"""Ingest ticks of whole papers through a hybrid state-space trunk embedder,
closed loop.

Everything of the tick is imported, nothing copied: the tick, its window,
end-to-end metric, counts and sample are ``ingest_ticks``'s, the router's
judgement ``chunk_ingest_ticks``'s, and the set-up's shape (one tick of each
*plan* warmed), the replay **through the plan** and the comparison itself
(``vec_err`` following the program's experts, ``route_gap``, ``replay_err``,
``topk_gap``, ``score_err``, ``e2e_gap``, ``stale_probes``) are
``doc_ingest_ticks``'s. Its own are:

* the embedder: built with ``trunk=`` once the program has said it knows the
  ``mamba2`` kind by name (``harness/sut_ssm.py``), with the benchmark's
  weights (``harness/weights_ssm.py``);
* the reference (``harness/reference_ssm.py``: the recurrence position by
  position) and its two controls: the reference at fp8 (``control=True`` or
  ``"fp8"``) and the float32 reference whose state is zero at every 256th
  position (``control="no_carry"``). Every sampled document is longer than a
  chunk, so both have to come out over a limit. ``route_gap`` is judged on
  the router's logits (the choice is the top 10 of those);
* **every seed is dealt the same work.** ``--seed`` orders the ticks and picks
  the probes, and a forward costs what its rung costs (85 ms on the 2,048
  rung, 670 on the 16,384 one), so a window that closes with the tick that
  crosses ``--seconds`` reads which ticks the seed left outside it and which
  documents it probed: 5% between the quartiles of the first twelve runs on
  the chip, with nothing but the seed between them. Here the window closes at
  the end of the pass in which ``--seconds`` run out (``_WholePasses``: every
  run forwards the mix's 64 documents, in its seed's order), and a tick's
  probe is drawn from the seed among the tick's documents on the rung that
  the mix's ``shape_seed`` drew for that tick (``equal_probes``: every run
  probes the same rungs).
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks.drivers import doc_ingest_ticks
from benchmarks.drivers.common import _pow2
from benchmarks.drivers.doc_ingest_ticks import (  # noqa: F401
    counts,
    end_to_end,
    release,
    sample,
)
from benchmarks.harness import reference_ssm, sut_ssm
from benchmarks.harness.traffic import word_count


def _with(function, **names):
    """``function`` of ``doc_ingest_ticks`` reading ``names`` where it reads
    its module's own: the driver's code as it stands, over another
    configuration's embedder and reference."""
    return types.FunctionType(
        function.__code__, {**vars(doc_ingest_ticks), **names}, function.__name__, function.__defaults__
    )


def equal_probes(traffic: dict, first_pass: list[list[str]], draws: np.ndarray) -> np.ndarray:
    """Which document each tick probes, ``draws`` being the seed's draw a
    tick: one of the tick's documents on the rung that the mix's
    ``shape_seed`` drew for a tick of these word counts (one of its documents,
    each as likely), so a pass probes the same rungs whatever the seed. A
    later pass has the first one's shapes in the first one's order."""
    out, per_pass = np.empty_like(draws), len(first_pass)
    for at, texts in enumerate(first_pass):
        words = [word_count(t) for t in texts]
        rungs = np.array([_pow2(n + 1) for n in words])
        drawn = np.random.default_rng(
            np.random.SeedSequence([int(traffic["shape_seed"]), 6, *words])
        ).integers(len(texts))
        on_rung = np.flatnonzero(rungs == rungs[drawn])
        out[at::per_pass] = on_rung[draws[at::per_pass] % len(on_rung)]
    return out


def setup(run) -> dict:
    """``doc_ingest_ticks.setup`` with this cell's embedder and weights in its
    own's place, and the probes it drew held to the mix's rungs."""
    state = _with(doc_ingest_ticks.setup, sut_gqa=sut_ssm)(run)
    state["probes"] = equal_probes(run.traffic, state["ticks"].first_pass(), state["probes"])
    return state


class _WholePasses:
    """``run`` as the imported window sees it, but for when the window is
    over: at the end of the pass in which ``--seconds`` run out, not with the
    tick that crosses them. Documents a second stay documents over the
    window's seconds (``run.window_s`` ends with the last tick)."""

    def __init__(self, run, per_pass: int):
        self._run, self._per_pass, self._out = run, per_pass, False

    def __getattr__(self, name):
        return getattr(self._run, name)

    def tick_done(self, work: dict) -> bool:
        self._out = self._run.tick_done(work) or self._out
        return self._out and len(self._run.ticks) % self._per_pass == 0


def window(run, state):
    records = doc_ingest_ticks.window(_WholePasses(run, state["ticks"].per_pass), state)
    state["window"] = (run, records)  # ``release`` reads the run itself
    return records


def reference_vectors(run, params, texts, forced, mode="f32"):
    cfg = run.config
    return reference_ssm.embed(
        params, texts, cfg, max_len=int(cfg["embedder"]["max_len"]), mode=mode, forced=forced
    )


check_numbers = _with(doc_ingest_ticks.check_numbers, reference_vectors=reference_vectors)


def check(run, state, records) -> dict:
    release(state)
    return check_numbers(run, state, records)
