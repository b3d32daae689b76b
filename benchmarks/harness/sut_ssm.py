"""The calls into the system under test for a hybrid state-space trunk
configuration: the embedder built with ``trunk=``, its seeded weights (made
by the benchmark, ``weights_ssm.py``) and, through ``sut_gqa.forward_again``,
the router's choices of the forwards a batch really rode in. With ``sut.py``,
``sut_trunk.py`` and ``sut_gqa.py`` the only importers of ``pathway_tpu``; no
``PATHWAY_*`` variable.

A program without the ``mamba2`` kind fails in ``build_embedder`` at once,
before anything is built: it is asked for the kind by name.
"""

from __future__ import annotations

from benchmarks.harness import sut_trunk
from benchmarks.harness.sut_gqa import forward_again  # noqa: F401
from benchmarks.harness.weights_ssm import make_params

KINDS = {"mamba": "mamba2", "attention": "gqa_full"}


def build_embedder(config: dict, name: str):
    """The embedder over the configuration's published keys, as a pipeline
    would build it (``sut_trunk.build_embedder``), once the program has said
    it knows the kinds, and with the file's table and share."""
    from pathway_tpu.xpacks.llm import _trunk

    missing = sorted(set(KINDS.values()) - set(_trunk.ATTENTION))
    if missing:
        raise SystemExit(f"this program's trunk has no {missing} kind: it cannot build {name!r}")
    embedder = sut_trunk.build_embedder(config, name)
    runtime = embedder.runtime
    kinds = [kinds.attention for kinds in runtime.config.layer_table()]
    want = [KINDS[t] for t in config["layer_types"]]
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
    runtime.params = make_params(template, seed, float(runtime.config.embedding_multiplier))
    return runtime.params
