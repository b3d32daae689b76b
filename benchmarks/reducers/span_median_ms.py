"""Median over the traced ticks of the milliseconds a tick spent in the named
harness spans (summed within a tick)."""

import statistics


def reduce(context, spans):
    per_tick = context.per_tick_ms(spans)
    return statistics.median(per_tick) if per_tick else None
