"""Ingest ticks, closed loop: embed a tick's chunks, upsert them, probe.

A tick is the batch of document chunks the engine hands the embedder in one
call (``_embed_batch``), followed by one ``TpuDenseKnnIndex.upsert`` per row
and one probe: the text of one chunk of this tick, embedded and searched.
The probe is the freshness promise (a row upserted before a search is visible
to it) and is what makes the index pay for the change. A document counts once
its tick's probe has answered.
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
    cfg, ticks, corpus = run.config, state["ticks"], state["corpus"]
    embedder, index, rows = state["embedder"], state["index"], len(corpus)
    first = ticks.first_pass()
    free = int(cfg["index"]["reserved_space"]) - rows
    max_ticks = free // max(len(t) for t in first)  # the index never grows
    probes = np.random.default_rng(np.random.SeedSequence([run.seed, 5])).integers(
        0, 2**31, size=max_ticks
    )
    state.update(max_ticks=max_ticks, probes=probes, next_key=rows)
    # warm: each class of chunk batch, each class a probe text can be (a
    # later pass has the first one's shapes), one refresh
    for texts in one_per_class(first):
        embedder._embed_batch(texts)
    for texts in one_per_class([[text] for tick in first for text in tick]):
        vector = embedder._embed_batch(texts)[0]
    index.search([(vector, state["k"], None)])  # uploads and prepares
    index.upsert(0, corpus[0], None)  # an unchanged row: a refresh, no new key
    index.search([(vector, state["k"], None)])
    run.phase("warm")
    return state


def _tick(run, state, number: int, texts: list[str]) -> TickRecord:
    embedder, index, k = state["embedder"], state["index"], state["k"]
    probe = int(state["probes"][number]) % len(texts)
    base = state["next_key"]
    t0 = time.perf_counter()
    with run.spans.span("embed", number):
        vectors = embedder._embed_batch(texts)
    with run.spans.span("upsert", number):
        for j, vector in enumerate(vectors):
            index.upsert(base + j, vector, None)
    with run.spans.span("probe_embed", number):
        probe_vector = embedder._embed_batch([texts[probe]])[0]
    with run.spans.span("probe_search", number):
        hits = index.search([(probe_vector, k, None)])
    state["next_key"] = base + len(texts)
    return TickRecord(
        number, t0, time.perf_counter(), texts, vectors, hits, probe, probe_vector
    )


def window(run, state) -> list[TickRecord]:
    cfg, ticks, records = run.config, state["ticks"], []
    dim, max_len = int(cfg["index"]["dimensions"]), int(cfg["max_position_embeddings"])
    while len(records) < state["max_ticks"]:  # the window closes early once the index is full
        texts = ticks[len(records)]
        records.append(_tick(run, state, len(records), texts))
        work = {
            "topk": [(1, state["next_key"], dim, state["k"])],
            "encoder_tokens": [tokens_of(t, max_len) for t in texts]
            + [tokens_of(texts[records[-1].probe], max_len)],
        }
        if run.tick_done(work):
            break
    return records


def end_to_end(run, records) -> dict:
    return {"ingest_docs_per_s": sum(len(r.texts) for r in records) / run.window_s}


def counts(records) -> tuple[int, int]:
    attempted = sum(len(r.texts) for r in records)
    done = sum(len(r.vectors) for r in records if r.hits and r.hits[0])
    return attempted, attempted - done


def sample(run, records) -> list[TickRecord]:
    """Whole ticks for the encoder: the one that holds the longest chunk and
    ``check_ticks`` more, drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([run.seed, 4]))
    longest = max(records, key=lambda r: max(len(t) for t in r.texts))
    picked = {longest.tick: longest}
    for i in rng.permutation(len(records)):
        if len(picked) > int(run.traffic["check_ticks"]):
            break
        picked.setdefault(records[i].tick, records[i])
    return [picked[t] for t in sorted(picked)]


def check_numbers(run, state, records, control=False) -> dict:
    k, corpus, params = state["k"], state["corpus"], state["params"]
    rows0, per_tick = len(corpus), len(records[0].texts)
    # the encoder: every chunk of the sampled ticks, and every probe
    picked = sample(run, records)
    texts = [t for r in picked for t in r.texts] + [r.texts[r.probe] for r in records]
    vectors = np.stack(
        [v for r in picked for v in r.vectors] + [r.probe_vector for r in records]
    )
    want = checks.reference_vectors(run, params, texts)
    shown = checks.reference_vectors(run, params, texts, "fp8") if control else vectors
    numbers = checks.encoder_numbers(want, shown)

    # the index: every probe of the window against the rows it could see
    rows_host = np.concatenate(
        [corpus] + [np.stack(r.vectors) for r in records]
    ).astype(np.float32, copy=False)
    row_tick = np.concatenate(
        [np.full(rows0, -1, np.int32)]
        + [np.full(len(r.vectors), r.tick, np.int32) for r in records]
    )
    ids, scores = served_arrays([r.hits[0] if r.hits else () for r in records], k)
    own = np.array([rows0 + r.tick * per_tick + r.probe for r in records])
    stale = int((ids != own[:, None]).all(axis=1).sum())
    numbers.update(
        checks.index_numbers(
            np.stack([r.probe_vector for r in records]),
            np.array([r.tick for r in records], np.int32),
            rows_host,
            row_tick,
            ids,
            scores,
            k,
            control,
        )
    )
    if not control:
        numbers["stale_probes"] = stale

    # both layers at once: every probe asked with the reference's own vector
    # of its text, over the reference's own rows for the sampled ticks
    probes_at = sum(len(r.texts) for r in picked)
    at = 0
    for r in picked:
        first = rows0 + r.tick * per_tick
        rows_host[first : first + len(r.texts)] = want[at : at + len(r.texts)]
        at += len(r.texts)
    numbers.update(
        checks.cross_numbers(
            want[probes_at:],
            np.array([r.tick for r in records], np.int32),
            rows_host,
            row_tick,
            ids,
            k,
            shown[probes_at:] if control else None,
        )
    )
    return numbers


def check(run, state, records) -> dict:
    release(state)
    return check_numbers(run, state, records)
