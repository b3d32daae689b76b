"""The share of the traced window in which the device ran nothing while the
program was inside the named span (its child spans included).

Every idle second of the window goes to the innermost program span that was
open during it (``idle_s_by_span``; ``(none)`` where none was), so the sum
over the names is the window's idle time. ``tick_ms_by_span`` gives, for
each program span, the median milliseconds a tick spent in it and in its self
time: the whole split of a tick in one place."""

import statistics

from benchmarks.harness import program_spans


def reduce(context, span):
    window = context.trace.window
    if window is None or not context.trace.device_ops:
        return None
    recorded = program_spans.read(context)
    if recorded is None:
        return None
    program_spans.per_tick(recorded, [span], len(context.ticks))  # named, so it has to be there
    offset, spread = program_spans.clock_offset(context)
    charged = program_spans.charge_gaps(program_spans.idle_gaps(context.trace), recorded, offset)
    share = 100.0 * program_spans.idle_inside(charged, recorded, span) / (window[1] - window[0])
    return share, {
        "idle_s_by_span": program_spans.idle_by_span(charged),
        "tick_ms_by_span": tick_ms_by_span(context, recorded),
        # how far the paired harness spans disagree on perf_counter against the trace's clock
        "host_clock_spread_ms": spread * 1e3,
    }


def tick_ms_by_span(context, recorded) -> dict[str, float]:
    parents = {s.parent_id for s in recorded}
    own = program_spans.self_seconds(recorded)
    totals: dict[str, list[float]] = {}
    for s in recorded:
        names = [s.name] + ([s.name + " (self)"] if s.span_id in parents else [])
        for name, seconds in zip(names, [s.seconds, own[s.span_id]]):
            per_tick = totals.setdefault(name, [0.0] * len(context.ticks))
            per_tick[s.tick] += seconds * 1e3
    return {name: statistics.median(per_tick) for name, per_tick in sorted(totals.items())}
