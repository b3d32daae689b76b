"""The calls into the system under test for a window-with-sinks trunk
configuration: the embedder built with ``trunk=``, its seeded weights (made
by the benchmark, ``weights_swa.py``) and, through ``sut_gqa.forward_again``,
the router's choices of the forwards a batch really rode in. With ``sut.py``,
``sut_trunk.py``, ``sut_gqa.py``, ``sut_ssm.py`` and ``sut_gdn.py`` the only
importers of ``pathway_tpu``; no ``PATHWAY_*`` variable.

A program without the ``swa_sink`` and ``gqa_partial`` kinds fails in
``build_embedder`` at once, before anything is built: it is asked for the
kinds by name.
"""

from __future__ import annotations

from benchmarks.harness import sut_trunk
from benchmarks.harness.reference_swa import layer_kinds
from benchmarks.harness.sut_gqa import forward_again  # noqa: F401
from benchmarks.harness.weights_swa import make_params

KINDS = {"window": "swa_sink", "full": "gqa_partial"}


def build_embedder(config: dict, name: str):
    """The embedder over the configuration's published keys, as a pipeline
    would build it (``sut_trunk.build_embedder``), once the program has said
    it knows the kinds, and with the table ``hybrid_layer_pattern`` gives and
    the file's share."""
    from pathway_tpu.xpacks.llm import _trunk

    missing = sorted(set(KINDS.values()) - set(_trunk.ATTENTION))
    if missing:
        raise SystemExit(f"this program's trunk has no {missing} kind: it cannot build {name!r}")
    embedder = sut_trunk.build_embedder(config, name)
    runtime = embedder.runtime
    kinds = [kinds.attention for kinds in runtime.config.layer_table()]
    want = [KINDS[kind] for kind in layer_kinds(config)]
    if kinds != want or runtime.config.held != tuple(config["experts_held"]):
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
