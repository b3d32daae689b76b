"""Query ticks, closed loop: embed the tick's query texts, search the index.

A tick is the batch of queries the engine hands the two device layers in one
call each: ``SentenceTransformerEmbedder._embed_batch(texts)`` and then
``TpuDenseKnnIndex.search([(vector, k, None), ...])``. Both are synchronous
and end in host arrays, so the host clock around them is a sound time. The
corpus does not change inside the window.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.drivers.common import (
    TickRecord,
    build,
    one_per_class,
    release,
    served_arrays,
    tokens_of,
)
from benchmarks.harness import check as checks


def setup(run) -> dict:
    state = build(run)
    ticks = state["ticks"]
    for texts in one_per_class(ticks.first_pass()):  # the first search uploads and prepares
        _tick(run, state, -1, texts)
    run.spans.records.clear()
    run.phase("warm")
    return state


def _tick(run, state, number: int, texts: list[str]) -> TickRecord:
    k = state["k"]
    t0 = time.perf_counter()
    with run.spans.span("embed", number):
        vectors = state["embedder"]._embed_batch(texts)
    with run.spans.span("search", number):
        hits = state["index"].search([(v, k, None) for v in vectors])
    return TickRecord(number, t0, time.perf_counter(), texts, vectors, hits)


def window(run, state) -> list[TickRecord]:
    cfg, ticks, records = run.config, state["ticks"], []
    rows, dim = int(cfg["index"]["rows_resident"]), int(cfg["index"]["dimensions"])
    max_len = int(cfg["max_position_embeddings"])
    while True:
        texts = ticks[len(records)]  # a later pass: the same shapes, other words
        records.append(_tick(run, state, len(records), texts))
        work = {
            "topk": [(len(texts), rows, dim, state["k"])],
            "encoder_tokens": [tokens_of(t, max_len) for t in texts],
        }
        if run.tick_done(work):
            return records


def end_to_end(run, records) -> dict:
    ms = np.array([(r.t1 - r.t0) * 1e3 for r in records])
    return {
        "retrieve_p50_ms": float(np.percentile(ms, 50)),
        "retrieve_p95_ms": float(np.percentile(ms, 95)),
        "retrieve_qps": sum(len(r.texts) for r in records) / run.window_s,
    }


def counts(records) -> tuple[int, int]:
    attempted = sum(len(r.texts) for r in records)
    answered = sum(len(r.hits) for r in records)
    return attempted, attempted - answered


def sample(run, records) -> list[TickRecord]:
    """Finished ticks drawn from the seed, the one with the longest text
    first, until ``check_queries`` queries are in."""
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 4]))
    longest = max(records, key=lambda r: (max(len(t) for t in r.texts), len(r.texts)))
    order = [longest] + [records[i] for i in rng.permutation(len(records))]
    want, picked, seen = int(run.traffic["check_queries"]), [], set()
    for record in order:
        if record.tick in seen or sum(len(r.texts) for r in picked) >= want:
            continue
        seen.add(record.tick)
        picked.append(record)
    return picked


def check_numbers(run, state, records, control=False) -> dict:
    picked = sample(run, records)
    k, corpus = state["k"], state["corpus"]
    texts = [t for r in picked for t in r.texts]
    vectors = np.stack([v for r in picked for v in r.vectors])
    ids, scores = served_arrays([h for r in picked for h in r.hits], k)
    want = checks.reference_vectors(run, state["params"], texts)
    shown = checks.reference_vectors(run, state["params"], texts, "fp8") if control else vectors
    numbers = checks.encoder_numbers(want, shown)
    tick, row_tick = np.zeros(len(texts), np.int32), np.full(len(corpus), -1, np.int32)
    numbers.update(checks.index_numbers(vectors, tick, corpus, row_tick, ids, scores, k, control))
    numbers.update(
        checks.cross_numbers(want, tick, corpus, row_tick, ids, k, shown if control else None)
    )
    return numbers


def check(run, state, records) -> dict:
    release(state)
    return check_numbers(run, state, records)
