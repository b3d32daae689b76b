"""Ingest ticks of whole papers through a gated-delta-rule trunk embedder,
closed loop.

Everything of the tick is imported, nothing copied: the set-up, the window
closing with its pass (``_WholePasses``) and the probes held to the mix's
rungs (``equal_probes``) are ``paper_ingest_ticks``'s, and the tick, its
end-to-end metric, counts and sample, the replay **through the plan** and the
comparison itself (``vec_err`` following the program's experts,
``route_gap``, ``replay_err``, ``topk_gap``, ``score_err``, ``e2e_gap``,
``stale_probes``) are ``doc_ingest_ticks``'s, each run here over this cell's
names through ``_with``. Its own are:

* the embedder: built with ``trunk=`` once the program has said it knows the
  ``gated_deltanet`` and ``gqa_gated`` kinds by name (``harness/sut_gdn.py``),
  with the benchmark's weights (``harness/weights_gdn.py``);
* the reference (``harness/reference_gdn.py``: the delta rule position by
  position) and its two controls: the reference at fp8 (``control=True`` or
  ``"fp8"``) and the float32 reference whose state is zero at every 64th
  position (``control="no_carry"``). Every sampled document is longer than a
  chunk, so both have to come out over a limit. ``route_gap`` is judged on
  the router's logits (the choice is the top 10 of those);
* the router's judgement a few texts at a time: the reference's logits are
  512 a position and layer, float16 on the host, and ``route_numbers`` and
  ``top_choice`` are ``chunk_ingest_ticks``'s, called on ``TEXTS_AT_ONCE``
  texts of like length at a time over their real positions, in float32 (the
  widest gap is the widest of the groups' widest), so that no temporary of
  a whole window's logits is made and numpy's slow float16 is not sorted.
"""

from __future__ import annotations

import numpy as np

from benchmarks.drivers import chunk_ingest_ticks, doc_ingest_ticks, paper_ingest_ticks
from benchmarks.drivers.doc_ingest_ticks import (  # noqa: F401
    counts,
    end_to_end,
    release,
    sample,
)
from benchmarks.drivers.paper_ingest_ticks import _WholePasses, _with, equal_probes, window  # noqa: F401
from benchmarks.harness import reference_gdn, sut_gdn

TEXTS_AT_ONCE = 8


def setup(run) -> dict:
    """``paper_ingest_ticks.setup`` with this cell's embedder and weights."""
    return _with(paper_ingest_ticks.setup, **{**vars(paper_ingest_ticks), "sut_ssm": sut_gdn})(run)


def reference_vectors(run, params, texts, forced, mode="f32"):
    cfg = run.config
    return reference_gdn.embed(
        params, texts, cfg, max_len=int(cfg["embedder"]["max_len"]), mode=mode, forced=forced
    )


def _groups(scores):
    """Texts of like length together, ``TEXTS_AT_ONCE`` at a time, and the
    positions that hold a real token in any of them."""
    real = (~np.isnan(scores[0, :, :, 0])).sum(axis=1)  # [texts]
    order = np.argsort(real)
    for at in range(0, len(order), TEXTS_AT_ONCE):
        texts = np.sort(order[at : at + TEXTS_AT_ONCE])
        yield texts, max(int(real[texts].max()), 1)


def route_numbers(choice, scores, k: int) -> dict:
    """``chunk_ingest_ticks.route_numbers`` group by group, on the group's
    real positions in float32: the widest gap of all (a position without a
    token has a gap of 0)."""
    gaps = [
        chunk_ingest_ticks.route_numbers(choice[:, texts, :width], scores[:, texts, :width].astype(np.float32), k)
        for texts, width in _groups(scores)
    ]
    return {"route_gap": max(g["route_gap"] for g in gaps)}


def top_choice(scores, k: int):
    """``chunk_ingest_ticks.top_choice`` group by group (0 where a text has no token)."""
    out = np.zeros(scores.shape[:-1] + (k,), np.int64)
    for texts, width in _groups(scores):
        part = scores[:, texts, :width].astype(np.float32)
        out[:, texts, :width] = chunk_ingest_ticks.top_choice(part, k)
    return out


check_numbers = _with(
    doc_ingest_ticks.check_numbers, reference_vectors=reference_vectors, route_numbers=route_numbers, top_choice=top_choice
)
check = _with(paper_ingest_ticks.check, release=release, check_numbers=check_numbers)
