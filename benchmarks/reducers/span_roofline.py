"""A kernel's share of its roofline: the least time the chip could take for
the calls' work (``harness/work.py``, from shapes, at the stated precision)
over the device-busy time inside the harness spans that made the calls."""

from benchmarks.harness import trace as tracing
from benchmarks.harness import work


def reduce(context, spans, calls):
    if context.peaks is None or not context.trace.device_ops:
        return None
    if calls != "topk":
        raise ValueError(f"no work function for calls of kind {calls!r}")
    least, bounds = 0.0, {}
    for tick in context.ticks:
        for batch, rows, dim, k in tick.get("topk", []):
            seconds, bound = work.least_time(
                work.topk_flops(batch, rows, dim),
                work.topk_bytes(batch, rows, dim, k),
                context.peaks,
            )
            least += seconds
            bounds[bound] = bounds.get(bound, 0.0) + seconds
    window = context.trace.window
    inside = [
        (a, b)
        for a, b in tracing.spans_named(context.trace, spans)
        if window is None or (a >= window[0] and b <= window[1])
    ]
    busy = tracing.mean_busy(context.trace, inside)
    if not busy or not least:
        return None
    return 100.0 * least / busy, {"bound": max(bounds, key=bounds.get)}
