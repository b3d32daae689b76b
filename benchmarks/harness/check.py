"""The comparison that decides ``correct``: the timed path's own output
against the plain reference, each number beside its limit.

* ``vec_err``: the widest L2 distance between a served unit vector and the
  reference's vector of the same text (tokenizer and encoder forward).
* ``topk_gap``: over the sampled queries and the k ranks, the widest gap by
  which the reference's score of the row served at a rank lies below the
  reference's own score at that rank, the query being the served vector
  (the index: scan, mask, top-k, key mapping, visibility). An answer that is
  not there is charged ``reference.MISSING``.
* ``score_err``: the widest distance between a served score and the
  reference's score of the same row.
* ``e2e_gap``: ``topk_gap`` once more with nothing the program prepared on the
  reference's side: the query is the reference's own vector of the query
  text, and the rows are the reference's too where the window made them (a
  sampled ingest tick). One comparison across both layers, text in, rows out.

``control=True`` puts the reference at fp8 in the program's place instead:
its vectors for the served ones, the rows it ranks first for the served rows,
and its scores of them. It has to come out over a limit.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import reference

QUERY_PAD = 64  # queries go to the reference in multiples of this many


def reference_vectors(run, params, texts, mode="f32") -> np.ndarray:
    """The reference's unit vectors [n, dim] of ``texts`` (``fp8``: the control's)."""
    cfg = run.config
    return reference.embed(
        params,
        texts,
        mode=mode,
        vocab_size=int(cfg["vocab_size"]),
        max_len=int(cfg["max_position_embeddings"]),
        depth=int(cfg["num_hidden_layers"]),
    )


def encoder_numbers(want, served) -> dict:
    """``vec_err`` of served vectors [n, dim] against the reference's."""
    err = np.linalg.norm(np.asarray(served, np.float64) - want, axis=1)
    return {"vec_err": float(err.max())}


def _pad(array: np.ndarray, fill) -> np.ndarray:
    short = -len(array) % QUERY_PAD
    if not short:
        return array
    tail = np.full((short,) + array.shape[1:], fill, dtype=array.dtype)
    return np.concatenate([array, tail])


def index_numbers(
    queries, query_tick, rows_host, row_tick, served_ids, served_scores, k, control=False
) -> dict:
    """``topk_gap`` and ``score_err`` of served answers. ``served_ids`` are
    rows of ``rows_host`` ([n, k], -1 where no answer came), ``served_scores``
    the served cosine similarities beside them."""
    n = len(queries)
    q = _pad(np.asarray(queries, np.float32), 0.0)
    q_tick = _pad(np.asarray(query_tick, np.int32), np.int32(-2))  # padding sees no row
    ids = _pad(np.asarray(served_ids, np.int64), -1)
    want = reference.best_scores(q, q_tick, rows_host, row_tick, k)[:n]
    if control:
        ids = reference.control_ids(q, q_tick, rows_host, row_tick, k).astype(np.int64)
        served_scores = reference.scores_of(q, rows_host, ids, mode="fp8")[:n]
    got = reference.scores_of(q, rows_host, ids)[:n]
    there = ids[:n] >= 0
    diff = np.abs(np.asarray(served_scores, np.float64) - got)
    return {
        "topk_gap": float((want - got).max()),
        "score_err": float(np.where(there, diff, reference.MISSING).max()),
    }


def cross_numbers(
    want_queries, query_tick, rows_host, row_tick, served_ids, k, control_queries=None
) -> dict:
    """``e2e_gap`` of served answers: ``want_queries`` are the reference's own
    vectors of the query texts, ``rows_host`` holds the reference's own rows
    wherever the window made rows. The control answers with the rows that fp8
    ranks first for its own fp8 vectors, ``control_queries``."""
    n = len(want_queries)
    q = _pad(np.asarray(want_queries, np.float32), 0.0)
    q_tick = _pad(np.asarray(query_tick, np.int32), np.int32(-2))
    ids = _pad(np.asarray(served_ids, np.int64), -1)
    want = reference.best_scores(q, q_tick, rows_host, row_tick, k)[:n]
    if control_queries is not None:
        shown = _pad(np.asarray(control_queries, np.float32), 0.0)
        ids = reference.control_ids(shown, q_tick, rows_host, row_tick, k).astype(np.int64)
    got = reference.scores_of(q, rows_host, ids)[:n]
    return {"e2e_gap": float((want - got).max())}


def with_limits(numbers: dict, limits: dict) -> dict:
    """Each number beside its limit; a number without a stated limit is exact."""
    return {
        name: {"value": value, "limit": limits.get(name, 0)}
        for name, value in numbers.items()
    }
