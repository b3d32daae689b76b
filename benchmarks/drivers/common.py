"""What the tick drivers share: the shape classes to warm, and tick records."""

from __future__ import annotations

import gc
from dataclasses import dataclass, field

import numpy as np

from benchmarks.harness import sut
from benchmarks.harness.traffic import TickStream, word_count


def build(run) -> dict:
    """What both tick drivers set up first: the embedder with seeded weights,
    the index loaded with the seeded corpus, and the seed's ticks."""
    cfg = run.config
    embedder = sut.build_embedder(cfg)
    run.phase("embedder")
    params = sut.seed_weights(embedder, run.seed)
    run.phase("weights")
    index = sut.build_index(cfg)
    rows, dim = int(cfg["index"]["rows_resident"]), int(cfg["index"]["dimensions"])
    corpus = sut.make_corpus(rows, dim, run.seed)
    run.phase("corpus")
    sut.load_corpus(index, corpus)
    run.phase("load")
    ticks = TickStream(run.traffic, run.seed)
    run.phase("traffic")
    return {
        "embedder": embedder,
        "index": index,
        "params": params,
        "corpus": corpus,
        "ticks": ticks,
        "k": int(cfg["index"]["k"]),
    }


def release(state) -> None:
    """Free the program's state before the reference runs."""
    state.pop("embedder", None)
    state.pop("index", None)
    gc.collect()


def served_arrays(answers, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Keys [n, k] (-1 where no answer came) and cosine similarities of the
    answers ``search`` gave, which serves a score as cos - 1."""
    ids = np.full((len(answers), k), -1, np.int64)
    scores = np.zeros((len(answers), k))
    for i, hits in enumerate(answers):
        for j, (key, score) in enumerate(hits[:k]):
            ids[i, j], scores[i, j] = key, score + 1.0
    return ids, scores


def tokens_of(text: str, max_len: int) -> int:
    """Real tokens of a generated text: its words are plain letters, so the
    hashing tokenizer gives one id a word, after the leading CLS."""
    return min(word_count(text) + 1, max_len)


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def shape_class(texts: list[str]) -> tuple[int, int]:
    """Batch and longest text, each rounded up to a power of two: calls of one
    class are taken to run the same compiled programs. The window proves it:
    a compilation inside it fails the run."""
    return _pow2(len(texts)), _pow2(max(word_count(t) for t in texts) + 1)


def one_per_class(batches: list[list[str]]) -> list[list[str]]:
    seen: dict[tuple[int, int], list[str]] = {}
    for texts in batches:
        seen.setdefault(shape_class(texts), texts)
    return list(seen.values())


@dataclass
class TickRecord:
    tick: int
    t0: float
    t1: float
    texts: list[str]
    vectors: list = field(default_factory=list)
    hits: tuple = ()
    probe: int | None = None  # ingest: which text of the tick was the probe
    probe_vector: object = None
