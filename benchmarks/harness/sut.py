"""The calls into the system under test, and nothing else of it.

This is the only harness file that imports ``pathway_tpu``. It builds the
embedder and the index the way the engine does, and sets no ``PATHWAY_*``
variable: the program runs at its defaults.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.harness.weights import make_params


def configure_compile_cache() -> str:
    """The program's own choice of directory (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), with every program kept, also the small
    ones, so that only a checkout's first run of a cell compiles."""
    import jax
    from pathway_tpu.internals.compile_cache import (
        configure_compile_cache as program_configure,
    )

    path = program_configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def build_embedder(config: dict):
    """The embedder at the configuration's widths, as the engine builds it."""
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    # At its defaults: the constructor looks for a cached tokenizer first (an
    # import of ``transformers``, some 23 s of set-up, PERF.md section 7),
    # finds none and takes the hashing tokenizer. Nothing is fetched.
    embedder = SentenceTransformerEmbedder(
        dim=int(config["hidden_size"]),
        depth=int(config["num_hidden_layers"]),
        heads=int(config["num_attention_heads"]),
        max_len=int(config["max_position_embeddings"]),
    )
    if embedder.tokenizer.vocab_size != int(config["vocab_size"]):
        raise RuntimeError(
            f"tokenizer has {embedder.tokenizer.vocab_size} ids, the "
            f"configuration states {config['vocab_size']}"
        )
    return embedder


def seed_weights(embedder, seed: int):
    """Weights from ``seed`` in place of the program's fixed-seed
    initialisation; the same tree goes to the reference.

    The program has no other way in for weights that are not a BERT
    checkpoint on disk, so this is the harness's one contract with the
    encoder's internals (PERF.md section 4): ``embedder.runtime.params`` is
    the flax tree every forward reads. A program that keeps derived copies
    has to derive them again when the attribute is set."""
    import jax

    runtime = embedder.runtime
    seeded = make_params(runtime.params, seed)
    if jax.tree_util.tree_structure(seeded) != jax.tree_util.tree_structure(runtime.params):
        raise RuntimeError("the seeded weights do not match the encoder's own tree")
    runtime.params = seeded
    return seeded


def build_index(config: dict):
    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

    ix = config["index"]
    return TpuDenseKnnIndex(
        dimensions=int(ix["dimensions"]),
        metric=ix["metric"],
        reserved_space=int(ix["reserved_space"]),
    )


def make_corpus(rows: int, dim: int, seed: int, chunk: int = 65536) -> np.ndarray:
    """Gaussian float32 rows from ``seed``, filled chunk by chunk on a few
    threads (numpy's generators release the interpreter lock)."""
    out = np.empty((rows, dim), dtype=np.float32)
    starts = list(range(0, rows, chunk))
    children = np.random.SeedSequence([seed, 0x636F72]).spawn(len(starts))

    def fill(job):
        start, child = job
        view = out[start : start + chunk]
        np.random.default_rng(child).standard_normal(
            view.shape, dtype=np.float32, out=view
        )

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, zip(starts, children)))
    return out


def load_corpus(index, corpus: np.ndarray) -> None:
    """Row ``i`` under key ``i``, through the call the engine makes per row."""
    upsert = index.upsert
    for key in range(corpus.shape[0]):
        upsert(key, corpus[key], None)
