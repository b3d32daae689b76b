"""Host spans around the calls into each layer, kept in memory.

Each span is also a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``,
so that a traced run finds the same interval on the profiler's clock and can
attribute the device's work to the call that launched it.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax


class Spans:
    def __init__(self):
        self.records: list[tuple[str, int, float, float]] = []  # name, tick, t0, t1

    @contextmanager
    def span(self, name: str, tick: int):
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, tick, t0, time.perf_counter()))

    def per_tick_ms(self, names: list[str]) -> list[float]:
        """Milliseconds per tick summed over the spans called ``names``."""
        totals: dict[int, float] = {}
        for name, tick, t0, t1 in self.records:
            if name in names:
                totals[tick] = totals.get(tick, 0.0) + (t1 - t0) * 1e3
        return [totals[t] for t in sorted(totals)]
