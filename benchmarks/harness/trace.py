"""From the profiler's trace to intervals, and the arithmetic on intervals.

A traced run records a few seconds of the steady window with
``jax.profiler``; the ``.xplane.pb`` it writes is read back with
``jax.profiler.ProfileData``. Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation that
ran on the chip, ``XLA Modules`` one per run of a compiled program. The
harness's own spans are the ``bench.*`` events of the host plane.

The two are not quite on one clock: on a v5e the device's stamps lay 1.4 to
2.3 ms before the host's (PERF.md section 3). The loop is single-threaded and
every call is synchronous, so each program a call launched runs wholly inside
that call's span; ``clock_offset`` finds, per device, the shift under which
most program runs do, and ``read`` applies it. The device time inside a span
is then the part of the device's busy union that the span's interval covers.
``read`` refuses a trace in which fewer than ``MIN_CONTAINED`` of the program
runs fit a span under the best shift: the attribution would not be sound.
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
MIN_CONTAINED = 0.99  # of a device's program runs, wholly inside one harness span

Interval = tuple[float, float]  # start, end, in seconds on the trace's clock


@dataclass
class Trace:
    """What the reducers read: per device the operations that ran, and the
    harness's spans, all in seconds on the profiler's clock."""

    device_ops: dict[str, list[tuple[float, float, str]]] = field(default_factory=dict)
    spans: list[tuple[float, float, str]] = field(default_factory=list)
    clock_offsets: dict[str, float] = field(default_factory=dict)  # added to device stamps
    contained: dict[str, float] = field(default_factory=dict)  # share of runs inside a span

    @property
    def window(self) -> Interval | None:
        marks = [s for s in self.spans if s[2] == SPAN_PREFIX + "window"]
        return (marks[0][0], marks[0][1]) if marks else None


def start(directory: str) -> None:
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the harness's spans are annotations
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def read(directory: str) -> Trace:
    import jax

    paths = sorted(glob.glob(os.path.join(directory, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    trace = Trace()
    modules: dict[str, list[Interval]] = {}

    def events(line):
        return [
            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events
        ]

    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.device_ops[plane.name] = events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [(a, b) for a, b, _ in events(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.spans += [e for e in events(line) if e[2].startswith(SPAN_PREFIX)]
    trace.spans.sort()
    calls = [(a, b) for a, b, name in trace.spans if name != SPAN_PREFIX + "window"]
    for device, ops in trace.device_ops.items():
        runs = modules.get(device, [])
        shift, share = clock_offset(runs, calls)
        if runs and calls and share < MIN_CONTAINED:
            raise RuntimeError(
                f"{device}: under the best clock shift ({shift * 1e3:.2f} ms) only "
                f"{share:.4f} of {len(runs)} program runs lie inside a harness span"
            )
        trace.clock_offsets[device] = shift
        trace.contained[device] = share
        trace.device_ops[device] = [(a + shift, b + shift, name) for a, b, name in ops]
    return trace


def clock_offset(
    runs: list[Interval], calls: list[Interval], reach: float = 10e-3, step: float = 50e-6
) -> tuple[float, float]:
    """The seconds to add to the device's stamps so that its program
    ``runs`` fall wholly inside the host's synchronous ``calls``, and the
    share of the runs that then do. Tried are the shifts within ``reach`` of
    none. Of those under which ``MIN_CONTAINED`` of the runs or more lie
    inside a call, the ones that leave the fewest calls without a run are
    kept: a wrong shift can put a short program into the tail of the call
    before its own, and then its own call stands empty. Of the stretches that
    remain, the one nearest to no shift is taken, and its middle. Where no
    shift places that many runs, the best one is returned with its share, for
    ``read`` to refuse."""
    if not runs or not calls:
        return 0.0, 1.0
    calls = sorted(calls)
    starts = [a for a, _ in calls]

    def placed(shift: float) -> tuple[int, int]:
        """Runs inside a call, and calls that hold a run."""
        holders = set()
        count = 0
        for a, b in runs:
            i = bisect.bisect_right(starts, a + shift) - 1
            if i >= 0 and b + shift <= calls[i][1]:
                count += 1
                holders.add(i)
        return count, len(holders)

    steps = list(range(-round(reach / step), round(reach / step) + 1))
    scores = [placed(i * step) for i in steps]
    enough = [j for j, (count, _) in enumerate(scores) if count >= MIN_CONTAINED * len(runs)]
    if not enough:
        best = max(range(len(steps)), key=lambda j: (scores[j], -abs(steps[j])))
        return steps[best] * step, scores[best][0] / len(runs)
    most = max(scores[j][1] for j in enough)
    kept = [j for j in enough if scores[j][1] == most]
    stretches = [[kept[0]]]
    for j in kept[1:]:
        if j == stretches[-1][-1] + 1:
            stretches[-1].append(j)
        else:
            stretches.append([j])
    nearest = min(stretches, key=lambda st: min(abs(steps[j]) for j in st))
    middle = nearest[len(nearest) // 2]
    return steps[middle] * step, scores[middle][0] / len(runs)


def union(intervals: list[Interval]) -> list[Interval]:
    """Disjoint sorted intervals that cover the same points."""
    out: list[list[float]] = []
    for start_s, end_s in sorted(intervals):
        if out and start_s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end_s)
        else:
            out.append([start_s, end_s])
    return [(a, b) for a, b in out]


def total(intervals: list[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: list[Interval], windows: list[Interval]) -> list[Interval]:
    """The parts of disjoint sorted ``intervals`` inside disjoint sorted
    ``windows``."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(windows) and windows[j][1] <= a:
            j += 1
        i = j
        while i < len(windows) and windows[i][0] < b:
            lo, hi = max(a, windows[i][0]), min(b, windows[i][1])
            if hi > lo:
                out.append((lo, hi))
            i += 1
    return out


def busy(trace: Trace, within: list[Interval] | None = None) -> dict[str, float]:
    """Seconds per device in which an operation ran, optionally only inside
    the given intervals (which need not be sorted or disjoint)."""
    windows = None if within is None else union(within)
    out = {}
    for device, ops in trace.device_ops.items():
        merged = union([(a, b) for a, b, _ in ops])
        out[device] = total(merged if windows is None else clip(merged, windows))
    return out


def mean_busy(trace: Trace, within: list[Interval] | None = None) -> float | None:
    per_device = busy(trace, within)
    if not per_device:
        return None
    return sum(per_device.values()) / len(per_device)


def spans_named(trace: Trace, names: list[str]) -> list[Interval]:
    wanted = {SPAN_PREFIX + n for n in names}
    return [(a, b) for a, b, name in trace.spans if name in wanted]


def top_device_ops(trace: Trace, within: Interval | None, n: int = 10) -> list[list]:
    sums: dict[str, float] = {}
    for ops in trace.device_ops.values():
        for a, b, name in ops:
            if within is not None:
                a, b = max(a, within[0]), min(b, within[1])
            if b > a:
                sums[name] = sums.get(name, 0.0) + (b - a)
    devices = max(1, len(trace.device_ops))
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:96], seconds / devices] for name, seconds in ranked]


def idle_gaps(trace: Trace, within: Interval | None, n: int = 10) -> list[list]:
    """The device's idle seconds by what the host was doing: each gap of the
    first device's busy union is charged to the harness span that covers its
    middle, or to ``between spans``."""
    if not trace.device_ops:
        return []
    ops = next(iter(trace.device_ops.values()))
    merged = union([(a, b) for a, b, _ in ops])
    if within is not None:
        merged = clip(merged, [within])
        edges = [within[0]] + [x for ab in merged for x in ab] + [within[1]]
    else:
        edges = [x for ab in merged for x in ab][1:-1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)]
    inner = [s for s in trace.spans if s[2] != SPAN_PREFIX + "window"]
    sums: dict[str, float] = {}
    j = 0
    for a, b in gaps:
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        while j < len(inner) and inner[j][1] < mid:
            j += 1
        name = "between spans"
        if j < len(inner) and inner[j][0] <= mid <= inner[j][1]:
            name = inner[j][2]
        sums[name] = sums.get(name, 0.0) + (b - a)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]
