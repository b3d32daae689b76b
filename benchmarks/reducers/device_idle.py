"""The device's idle share of the traced window: 1 minus the union of the
intervals in which an operation ran, averaged over the chips."""

from benchmarks.harness import trace as tracing


def reduce(context):
    window = context.trace.window
    if window is None or not context.trace.device_ops:
        return None
    busy = tracing.mean_busy(context.trace, [window])
    return 100.0 * (1.0 - busy / (window[1] - window[0]))
