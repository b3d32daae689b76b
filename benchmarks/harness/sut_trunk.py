"""The calls into the system under test for a trunk configuration: the
embedder built with ``trunk=``, its seeded weights (made by the benchmark,
``weights_trunk.py``) and its router's choices. With ``sut.py`` (index,
corpus, compile cache) the only importers of ``pathway_tpu``; no
``PATHWAY_*`` variable.

A program from before the trunk has no ``_trunk`` module: ``build_embedder``
fails on the import, at once, before anything is built.
"""

from __future__ import annotations

from benchmarks.harness.weights_trunk import make_params


def build_embedder(config: dict, name: str):
    """The embedder over the configuration's published keys, as a pipeline
    would build it: ``SentenceTransformerEmbedder(trunk=...)``."""
    from pathway_tpu.xpacks.llm._trunk import TrunkConfig
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    trunk = TrunkConfig.from_dict(config, name=name)
    embedder = SentenceTransformerEmbedder(
        model=name, trunk=trunk, max_len=int(config["embedder"]["max_len"])
    )
    runtime = embedder.runtime
    if runtime.dim != int(config["hidden_size"]) or not hasattr(runtime, "config"):
        raise RuntimeError(f"the embedder did not build the trunk: its runtime is {type(runtime).__name__}")
    if embedder.tokenizer.vocab_size != int(config["vocab_size"]):
        raise RuntimeError(
            f"tokenizer has {embedder.tokenizer.vocab_size} ids, the "
            f"configuration states {config['vocab_size']}"
        )
    return embedder


def seed_weights(embedder, seed: int):
    """Weights from ``seed`` in place of the program's own initialisation, set
    as ``embedder.runtime.params`` before the first forward, so the runtime
    never makes its own; the same arrays go to the plain reference. The
    program gives the tree's shape and nothing else (``jax.eval_shape`` of
    its ``init_params``: names, shapes, dtypes; no value is computed); the
    values are ``weights_trunk``'s."""
    import jax
    from pathway_tpu.xpacks.llm._trunk import init_params

    runtime = embedder.runtime
    template = jax.eval_shape(lambda: init_params(runtime.config, 0, runtime.dtype))
    runtime.params = make_params(template, seed)
    return runtime.params


def forward_again(embedder, texts):
    """What ``embed_batch`` serves for ``texts``, through the two calls it
    makes (``tokenizer.encode_batch``, ``runtime.forward``) and so through
    the compiled program of the timed path, this time asking the runtime for
    its router's choices as well: vectors [n, d] and the experts each token
    went to, [expert layers, n, positions, k] (-1: nowhere)."""
    runtime = embedder.runtime
    ids, mask = embedder.tokenizer.encode_batch([str(t) for t in texts], runtime.max_len)
    vectors, forwarded = runtime.forward(ids, mask, routing=True)
    return vectors, forwarded["expert_choice"]
