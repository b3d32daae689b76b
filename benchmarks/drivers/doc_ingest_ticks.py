"""Ingest ticks of whole documents through a grouped-query trunk embedder,
closed loop.

The tick is ``ingest_ticks``'s own (embed a tick's documents, upsert them,
probe with one of them), its window, end-to-end metric, counts and sample
likewise, and the comparison's pieces are ``chunk_ingest_ticks``'s
(``route_gap``, ``vec_err`` with the reference following the program's
experts, ``replay_err``): imported, not copied. Its own are:

* the set-up: the embedder is built with ``trunk=`` and asked for the
  grouped-query kinds by name first (``harness/sut_gqa.py``), and the warm-up
  runs one tick of each *plan*: a tick of documents of 1k-16k tokens never
  rides whole, the embedder forwards it rung by rung, and which shapes that
  takes follows from the rungs of all its documents, not from the longest;
* the replay: **through the plan**. This cell's batches are always split, so
  one forward of the whole batch is not the served program; the sampled
  batches go once more through the embedder's own planned forward, the
  groups it really forwarded, each asked for its router's choices
  (``sut_gqa.forward_again``);
* the reference (``harness/reference_gqa.py``) and two controls: the
  reference at fp8 (``control=True`` or ``"fp8"``) and the reference whose
  window layers see the whole row (``control="no_window"``): with documents
  longer than the window in every sample, both have to come out over a limit.
"""

from __future__ import annotations

import numpy as np

from benchmarks.drivers import common
from benchmarks.drivers.chunk_ingest_ticks import (  # noqa: F401
    encoder_numbers,
    route_numbers,
    sampled,
    top_choice,
    window,
)
from benchmarks.drivers.common import _pow2, one_per_class, served_arrays
from benchmarks.drivers.ingest_ticks import counts, end_to_end, sample  # noqa: F401
from benchmarks.harness import check as checks
from benchmarks.harness import reference_gqa, sut, sut_gqa
from benchmarks.harness.traffic import TickStream, word_count


def one_per_plan(batches: list[list[str]]) -> list[list[str]]:
    """One batch of each multiset of length rungs: the embedder's plan cuts a
    batch by its texts' rungs, so these ride in the same compiled programs."""
    seen: dict[tuple, list[str]] = {}
    for texts in batches:
        seen.setdefault(tuple(sorted(_pow2(word_count(t) + 1) for t in texts)), texts)
    return list(seen.values())


def setup(run) -> dict:
    cfg = run.config
    embedder = sut_gqa.build_embedder(cfg, run.cell.config_name)
    run.phase("embedder")
    params = sut_gqa.seed_weights(embedder, run.seed)
    run.phase("weights")
    index = sut.build_index(cfg)
    rows, dim = int(cfg["index"]["rows_resident"]), int(cfg["index"]["dimensions"])
    corpus = sut.make_corpus(rows, dim, run.seed)
    run.phase("corpus")
    sut.load_corpus(index, corpus)
    run.phase("load")
    ticks = TickStream(run.traffic, run.seed)
    run.phase("traffic")
    k = int(cfg["index"]["k"])
    first = ticks.first_pass()
    free = int(cfg["index"]["reserved_space"]) - rows
    max_ticks = free // max(len(t) for t in first)  # the index never grows
    probes = np.random.default_rng(np.random.SeedSequence([run.seed, 5])).integers(
        0, 2**31, size=max_ticks
    )
    # warm: each plan of a document batch, each class a probe text can be, one refresh
    for texts in one_per_plan(first):
        embedder._embed_batch(texts)
    for texts in one_per_class([[text] for tick in first for text in tick]):
        vector = embedder._embed_batch(texts)[0]
    index.search([(vector, k, None)])  # uploads and prepares
    index.upsert(0, corpus[0], None)  # an unchanged row: a refresh, no new key
    index.search([(vector, k, None)])
    run.phase("warm")
    return {
        "embedder": embedder, "index": index, "params": params, "corpus": corpus,
        "ticks": ticks, "k": k, "max_ticks": max_ticks, "probes": probes, "next_key": rows,
    }


def release(state) -> None:
    """Before the program's state is freed: the sampled texts once more
    through the embedder's plan, batch by batch as the window forwarded them,
    for the router's choices. Set-up and window are over; nothing here is timed."""
    embedder = state.get("embedder")
    if embedder is not None and "window" in state:
        run, records = state.pop("window")
        max_len = int(run.config["embedder"]["max_len"])
        batches = [r.texts for r in sample(run, records)] + [[r.texts[r.probe]] for r in records]
        vectors, choices = [], []
        for texts in batches:
            again, choice = sut_gqa.forward_again(embedder, texts)
            vectors.append(again)
            pad = max_len - choice.shape[2]
            choices.append(np.pad(choice, ((0, 0), (0, 0), (0, pad), (0, 0)), constant_values=-1))
        state["replay"] = (np.concatenate(vectors), np.concatenate(choices, axis=1))
    common.release(state)


def reference_vectors(run, params, texts, forced, mode="f32"):
    cfg = run.config
    return reference_gqa.embed(
        params, texts, cfg, max_len=int(cfg["embedder"]["max_len"]), mode=mode, forced=forced
    )


def check_numbers(run, state, records, control=False) -> dict:
    """``chunk_ingest_ticks.check_numbers`` with this cell's reference;
    ``control`` False, True / ``"fp8"`` or ``"no_window"``."""
    k, corpus, params = state["k"], state["corpus"], state["params"]
    experts_a_token = int(run.config["num_experts_per_tok"])
    rows0, per_tick = len(corpus), len(records[0].texts)
    picked, texts, vectors = sampled(run, records)
    again, choice = state["replay"]
    fp8 = control in (True, "fp8")

    # the encoder: the reference follows the program's experts, so the vectors
    # differ by arithmetic; the choice itself is judged by the reference's scores
    if "reference" not in state:
        state["reference"] = reference_vectors(run, params, texts, choice)
    want, scores = state["reference"]
    if control:
        shown, scores_control = reference_vectors(run, params, texts, choice, "fp8" if fp8 else control)
        judged = top_choice(np.nan_to_num(scores_control), experts_a_token)
    else:
        shown, judged = vectors, choice
    numbers = encoder_numbers(want, shown)
    numbers.update(route_numbers(judged, scores, experts_a_token))
    if not control:
        numbers["replay_err"] = float(np.linalg.norm(again.astype(np.float64) - vectors, axis=1).max())

    # the index: every probe of the window against the rows it could see
    rows_host = np.concatenate(
        [corpus] + [np.stack(r.vectors) for r in records]
    ).astype(np.float32, copy=False)
    row_tick = np.concatenate(
        [np.full(rows0, -1, np.int32)]
        + [np.full(len(r.vectors), r.tick, np.int32) for r in records]
    )
    query_tick = np.array([r.tick for r in records], np.int32)
    ids, scores_served = served_arrays([r.hits[0] if r.hits else () for r in records], k)
    own = np.array([rows0 + r.tick * per_tick + r.probe for r in records])
    numbers.update(
        checks.index_numbers(
            np.stack([r.probe_vector for r in records]), query_tick, rows_host, row_tick,
            ids, scores_served, k, fp8,
        )
    )
    if not control:
        numbers["stale_probes"] = int((ids != own[:, None]).all(axis=1).sum())

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
            want[probes_at:], query_tick, rows_host, row_tick, ids, k,
            shown[probes_at:] if control else None,
        )
    )
    return numbers


def check(run, state, records) -> dict:
    release(state)
    return check_numbers(run, state, records)
