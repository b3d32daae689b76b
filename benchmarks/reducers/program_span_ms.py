"""Median over the traced ticks of the milliseconds a tick spent in the named
program spans (summed within a tick), or in their self time.

Extras, where the metric's file asks: ``device_ms``, the device-busy time
inside those spans (median per tick, device trace), and per-tick sums of a
count the spans carry as an attribute."""

import statistics

from benchmarks.harness import program_spans


def reduce(context, spans, self_time=False, device_ms=False, per_tick_sums=None):
    recorded = program_spans.read(context)
    if recorded is None:
        return None
    ticks = program_spans.per_tick(recorded, spans, len(context.ticks))
    if self_time:
        own = program_spans.self_seconds(recorded)
        seconds = [sum(own[s.span_id] for s in tick) for tick in ticks]
    else:
        seconds = [sum(s.seconds for s in tick) for tick in ticks]
    extra = {}
    for name, attribute in (per_tick_sums or {}).items():
        extra[name] = statistics.median(
            sum(s.attributes[attribute] for s in tick) for tick in ticks
        )
    if device_ms and context.trace.device_ops:
        offset, _spread = program_spans.clock_offset(context)
        busy = program_spans.Busy(context.trace)
        extra["device_ms"] = 1e3 * statistics.median(
            sum(busy.inside(s.t0 + offset, s.t1 + offset) for s in tick) for tick in ticks
        )
    return 1e3 * statistics.median(seconds), extra
