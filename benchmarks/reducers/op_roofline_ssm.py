"""``op_roofline``'s reading for a hybrid state-space trunk configuration:
the least time the chip could take for the named work of the traced ticks
(``harness/work_ssm.py``) over the device time of the operations whose names
match the metric's patterns, inside the traced window.

The scan's work is counted sequence by sequence (its pairs stop at a chunk's
end, and a document's last chunk is part full), the experts' batch by batch
(their weights are read once a batch). Where no operation matches (a program
without the kernel) there is nothing to read and the metric is left out.
``op_roofline_gqa`` reads the same way over ``work_gqa``'s table; neither
takes the table as an argument, and a file the benchmark has is not edited
here."""

import re

from benchmarks.harness import work, work_ssm, work_trunk


def least_time(context, calls):
    flops_of, bytes_of, unit = work_ssm.WORK[calls]
    least, bounds = 0.0, {}
    for tick in context.ticks:
        for forward in work_trunk.tick_forwards(tick):
            for tokens in forward if unit == "sequence" else [sum(forward)]:
                seconds, bound = work.least_time(
                    flops_of(context.config, tokens), bytes_of(context.config, tokens), context.peaks
                )
                least += seconds
                bounds[bound] = bounds.get(bound, 0.0) + seconds
    return least, bounds


def reduce(context, patterns, calls):
    window = context.trace.window
    if context.peaks is None or window is None or not context.trace.device_ops:
        return None
    least, bounds = least_time(context, calls)
    wanted = [re.compile(p) for p in patterns]
    busy, matched = 0.0, {}
    for ops in context.trace.device_ops.values():
        for a, b, name in ops:
            a, b = max(a, window[0]), min(b, window[1])
            if b > a and any(p.search(name) for p in wanted):
                busy += b - a
                matched[name] = matched.get(name, 0.0) + (b - a)
    busy /= len(context.trace.device_ops)
    if not busy or not least:
        return None
    top = sorted(matched.items(), key=lambda kv: -kv[1])[:6]
    return 100.0 * least / busy, {
        "bound": max(bounds, key=bounds.get),
        "device_ms_per_tick": 1e3 * busy / len(context.ticks),
        "ops": [[name[:64], seconds] for name, seconds in top],
    }
