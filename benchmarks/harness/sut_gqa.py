"""The calls into the system under test for a grouped-query trunk
configuration: the embedder built with ``trunk=``, its seeded weights (made
by the benchmark, ``weights_gqa.py``) and, from the embedder's own plan, the
router's choices of the forwards a batch really rode in. With ``sut.py`` and
``sut_trunk.py`` the only importers of ``pathway_tpu``; no ``PATHWAY_*``
variable.

A program without the grouped-query kinds fails in ``build_embedder`` at
once, before anything is built: it is asked for them by name.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import sut_trunk
from benchmarks.harness.weights_gqa import make_params

KINDS = ("gqa_window", "gqa_full")


def build_embedder(config: dict, name: str):
    """The embedder over the configuration's published keys, as a pipeline
    would build it (``sut_trunk.build_embedder``), once the program has
    said it knows the kinds, and with the file's table and share."""
    from pathway_tpu.xpacks.llm import _trunk

    missing = [kind for kind in KINDS if kind not in _trunk.ATTENTION]
    if missing or "parallel" not in _trunk.RESIDUAL:
        raise SystemExit(
            f"this program's trunk has no {missing or ['parallel']} kind: it cannot build {name!r}"
        )
    embedder = sut_trunk.build_embedder(config, name)
    runtime = embedder.runtime
    kinds = [kinds.attention for kinds in runtime.config.layer_table()]
    want = [{"sliding_attention": "gqa_window", "full_attention": "gqa_full"}[t] for t in config["layer_types"]]
    if kinds != want[: len(kinds)] or runtime.config.held != tuple(config["experts_held"]):
        raise RuntimeError(f"the trunk's table {kinds} or share {runtime.config.held} is not the file's")
    return embedder


def seed_weights(embedder, seed: int):
    """Weights from ``seed`` in place of the program's own initialisation
    (``sut_trunk.seed_weights``'s contract: the program gives the tree's
    shape through ``jax.eval_shape``, the values are the benchmark's)."""
    import jax
    from pathway_tpu.xpacks.llm._trunk import init_params

    runtime = embedder.runtime
    template = jax.eval_shape(lambda: init_params(runtime.config, 0, runtime.dtype))
    runtime.params = make_params(template, seed)
    return runtime.params


def forward_again(embedder, texts):
    """What ``embed_batch`` serves for ``texts``, through the two calls it
    makes (``tokenizer.encode_batch``, then the embedder's own
    ``_forward_planned``: the plan of ``length_groups`` and one forward a
    group) and so through the compiled programs of the timed path, this time
    asking every forward for its router's choices: vectors [n, d] and the
    experts each token went to, [expert layers, n, positions, k] (-1:
    nowhere, and past a group's own rung)."""
    runtime = embedder.runtime
    ids, mask = embedder.tokenizer.encode_batch([str(t) for t in texts], runtime.max_len)
    lengths = mask.sum(axis=1).astype(np.int64)
    vectors, parts = embedder._forward_planned(ids, mask, lengths, routing=True)
    first = parts[0][1]["expert_choice"]
    choices = np.full(first.shape[:1] + ids.shape + first.shape[3:], -1, first.dtype)
    for rows, forwarded in parts:
        choice = forwarded["expert_choice"][:, : len(rows)]
        choices[:, rows, : choice.shape[2]] = choice
    return vectors, choices
