"""Ingest ticks of whole papers through a window-with-sinks trunk embedder,
closed loop.

Everything of the tick is imported, nothing copied: the set-up, the window
closing with its pass (``_WholePasses``) and the probes held to the mix's
rungs (``equal_probes``) are ``paper_ingest_ticks``'s; the tick, its
end-to-end metric, counts and sample, the replay **through the plan** and the
comparison itself (``vec_err`` following the program's experts,
``route_gap``, ``replay_err``, ``topk_gap``, ``score_err``, ``e2e_gap``,
``stale_probes``) are ``doc_ingest_ticks``'s, each run here over this cell's
names through ``_with``; the router's judgement a few texts at a time on
float16 scores is ``gdn_paper_ingest_ticks``'s. Its own are:

* the embedder: built with ``trunk=`` once the program has said it knows the
  ``swa_sink`` and ``gqa_partial`` kinds by name (``harness/sut_swa.py``),
  with the benchmark's weights (``harness/weights_swa.py``);
* the reference (``harness/reference_swa.py``: the sink one more logit of a
  row's softmax, the window as a mask) and its two controls: the reference at
  fp8 (``control=True`` or ``"fp8"``) and the float32 reference with every
  sink taken out (``control="no_sink"``). Every sampled document is longer
  than the window many times over, so both have to come out over a limit.
  ``route_gap`` is judged on the router's corrected scores (the choice is
  the top 8 of those).
"""

from __future__ import annotations

from benchmarks.drivers import doc_ingest_ticks, paper_ingest_ticks
from benchmarks.drivers.doc_ingest_ticks import (  # noqa: F401
    counts,
    end_to_end,
    release,
    sample,
)
from benchmarks.drivers.gdn_paper_ingest_ticks import route_numbers, top_choice
from benchmarks.drivers.paper_ingest_ticks import _with, equal_probes, window  # noqa: F401
from benchmarks.harness import reference_swa, sut_swa


def setup(run) -> dict:
    """``paper_ingest_ticks.setup`` with this cell's embedder and weights."""
    return _with(paper_ingest_ticks.setup, **{**vars(paper_ingest_ticks), "sut_ssm": sut_swa})(run)


def reference_vectors(run, params, texts, forced, mode="f32"):
    cfg = run.config
    return reference_swa.embed(
        params, texts, cfg, max_len=int(cfg["embedder"]["max_len"]), mode=mode, forced=forced
    )


check_numbers = _with(
    doc_ingest_ticks.check_numbers, reference_vectors=reference_vectors, route_numbers=route_numbers, top_choice=top_choice
)
check = _with(paper_ingest_ticks.check, release=release, check_numbers=check_numbers)
