"""The program's own spans, read beside the harness's.

The program records spans inside its two device layers in its tracer's ring
(``pathway_tpu/observability/tracing.py``): ``embed.batch`` > ``embed.tokenize``,
``embed.forward`` and ``index.search`` > ``corpus.upload``, ``corpus.prepare``,
``index.topk``, each with counts as attributes. A record's ``start_perf_ns``
is a ``time.perf_counter_ns`` read, and ``spans.py`` reads ``time.perf_counter``:
one clock, so a program span belongs to the tick of the harness span that
contains it and needs no fitting. The profiler's clock is another; the
``bench.*`` spans exist on both, and their median difference carries a
program span over to the device trace.

A program from before these spans (no ``start_perf_ns`` on its records) gives
nothing to read and every reader returns None. A program that has them and
lacks one a metric names, in any traced tick, is an error: a renamed span
must fail the traced run, not fall silent.
"""

from __future__ import annotations

import bisect
import statistics
from dataclasses import dataclass

from benchmarks.harness import trace as tracing

NO_SPAN = "(none)"
MAX_OFFSET_SPREAD = 0.2e-3  # seconds by which paired harness spans may disagree


@dataclass(frozen=True)
class ProgramSpan:
    name: str
    t0: float  # seconds on time.perf_counter's clock
    t1: float
    tick: int
    span_id: str
    parent_id: str | None
    attributes: dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def tracer_records() -> tuple[list, int]:
    """The ring as the program holds it, oldest first, and how many records
    it has overwritten (0 for a program that does not count them)."""
    from pathway_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    return tracer.spans(), int(getattr(tracer, "dropped", 0))


def traced_harness_spans(context) -> list[tuple[str, int, float, float]]:
    """The harness's spans of the traced ticks, in the order they ran."""
    return [r for r in context.spans.records if 0 <= r[1] < len(context.ticks)]


def read(context) -> list[ProgramSpan] | None:
    """The program spans of the traced ticks, each with its tick, by start;
    None where the program records none on the harness's clock."""
    records, dropped = tracer_records()
    return assign(context, records, dropped)


def assign(context, records: list, dropped: int = 0) -> list[ProgramSpan] | None:
    if not records or not hasattr(records[0], "start_perf_ns"):
        return None
    harness = traced_harness_spans(context)
    if not harness:
        return None
    # containment is decided in whole nanoseconds, as both clocks read them
    bounds = [(round(t0 * 1e9), round(t1 * 1e9)) for _name, _tick, t0, t1 in harness]
    starts = [start for start, _end in bounds]
    oldest_end = records[0].start_perf_ns + records[0].duration_ns
    if dropped and oldest_end > starts[0]:
        # the ring drops by end time: what ended before its oldest is gone
        raise RuntimeError(
            f"the program's span ring overwrote {dropped} records and its oldest "
            f"ended {(oldest_end - starts[0]) * 1e-9:.3f} s after the window began: the "
            "window has a hole (PATHWAY_TRACE_BUFFER is too small for it)"
        )
    out = []
    for record in records:
        start, end = record.start_perf_ns, record.start_perf_ns + record.duration_ns
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or end > bounds[i][1]:
            continue  # set-up, warm-up, or a tick after the traced part
        out.append(
            ProgramSpan(
                record.name, start * 1e-9, end * 1e-9, harness[i][1], record.span_id,
                record.parent_id, record.attributes,
            )
        )
    out.sort(key=lambda s: (s.t0, -s.t1))
    return out


def per_tick(spans: list[ProgramSpan], names: list[str], n_ticks: int) -> list[list[ProgramSpan]]:
    """The spans called ``names``, tick by tick. Raises where a traced tick
    has none: the metric that names them would otherwise fall silent."""
    ticks: list[list[ProgramSpan]] = [[] for _ in range(n_ticks)]
    for span in spans:
        if span.name in names:
            ticks[span.tick].append(span)
    for tick, found in enumerate(ticks):
        if not found:
            raise RuntimeError(
                f"traced tick {tick} has no program span {' or '.join(map(repr, names))}; "
                f"the program recorded {sorted({s.name for s in spans if s.tick == tick})} there"
            )
    return ticks


def self_seconds(spans: list[ProgramSpan]) -> dict[str, float]:
    """By span id, a span's time less what its child spans cover."""
    own = {s.span_id: s.seconds for s in spans}
    for s in spans:
        if s.parent_id in own:
            own[s.parent_id] -= s.seconds
    return own


def clock_offset(context) -> tuple[float, float]:
    """Seconds to add to a ``perf_counter`` time to land on the trace's
    clock: the median, over the traced ticks' harness spans, of where the
    profiler saw a span start less where the harness did; and the spread of
    those differences. Raises where the two do not pair up, or where the
    pairs disagree."""
    harness = traced_harness_spans(context)
    seen = [s for s in context.trace.spans if s[2] != tracing.SPAN_PREFIX + "window"]
    names = [tracing.SPAN_PREFIX + name for name, _tick, _t0, _t1 in harness]
    if not harness or names != [name for _a, _b, name in seen]:
        raise RuntimeError(
            f"the trace holds {len(seen)} harness spans, the harness recorded "
            f"{len(harness)} in the traced ticks, or their names differ"
        )
    differences = [a - t0 for (a, _b, _n), (_name, _tick, t0, _t1) in zip(seen, harness)]
    spread = spread_of(differences)
    if spread > MAX_OFFSET_SPREAD:
        raise RuntimeError(
            f"the harness spans disagree on the offset between perf_counter and the "
            f"trace's clock by {spread * 1e3:.3f} ms (limit {MAX_OFFSET_SPREAD * 1e3:.1f} ms)"
        )
    return statistics.median(differences), spread


def spread_of(differences: list[float]) -> float:
    """The width of the middle nine tenths: one span whose thread lost the
    core between the profiler's stamp and the clock's read is not a drift."""
    if len(differences) < 2:
        return 0.0
    cuts = statistics.quantiles(differences, n=20, method="inclusive")
    return cuts[-1] - cuts[0]


class Busy:
    """Device-busy seconds inside an interval, per device, by bisection over
    the union of the intervals in which an operation ran."""

    def __init__(self, trace: tracing.Trace):
        self.devices = []
        for ops in trace.device_ops.values():
            merged = tracing.union([(a, b) for a, b, _ in ops])
            total, sums = 0.0, [0.0]
            for a, b in merged:
                total += b - a
                sums.append(total)
            self.devices.append(([a for a, _ in merged], [b for _, b in merged], sums))

    def inside(self, start: float, end: float) -> float:
        """Mean over the devices of the busy seconds in ``[start, end]``."""
        per_device = []
        for starts, ends, sums in self.devices:
            i = bisect.bisect_right(ends, start)  # first interval that ends after start
            j = bisect.bisect_left(starts, end)  # first that starts at or after end
            if i >= j:
                per_device.append(0.0)
                continue
            busy = sums[j] - sums[i]
            busy -= max(0.0, start - starts[i]) + max(0.0, ends[j - 1] - end)
            per_device.append(busy)
        return sum(per_device) / len(per_device)


def idle_gaps(trace: tracing.Trace) -> list[tracing.Interval]:
    """The stretches of the traced window in which the first device ran
    nothing (the rule of ``trace.idle_gaps``)."""
    window = trace.window
    ops = next(iter(trace.device_ops.values()))
    merged = tracing.clip(tracing.union([(a, b) for a, b, _ in ops]), [window])
    edges = [window[0]] + [x for ab in merged for x in ab] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)]
    return [(a, b) for a, b in gaps if b > a]


def innermost_pieces(
    spans: list[ProgramSpan], offset: float
) -> list[tuple[float, float, ProgramSpan]]:
    """The time the spans cover, on the trace's clock, cut into disjoint
    pieces that each belong to the innermost span open there. ``spans`` are
    sorted by start, parents before their children, and nest (one thread)."""
    pieces: list[tuple[float, float, ProgramSpan]] = []
    open_spans: list[ProgramSpan] = []
    cursor = float("-inf")

    def close_until(limit: float) -> None:
        nonlocal cursor
        while open_spans and open_spans[-1].t1 + offset <= limit:
            top = open_spans.pop()
            if top.t1 + offset > cursor:
                pieces.append((cursor, top.t1 + offset, top))
                cursor = top.t1 + offset

    for span in spans:
        start = span.t0 + offset
        close_until(start)
        if open_spans and start > cursor:
            pieces.append((cursor, start, open_spans[-1]))
        open_spans.append(span)
        cursor = max(cursor, start)
    close_until(float("inf"))
    return pieces


def charge_gaps(
    gaps: list[tracing.Interval], spans: list[ProgramSpan], offset: float
) -> list[tuple[float, ProgramSpan | None]]:
    """Idle seconds with the innermost program span that was open during
    them, or None where none was: each gap is cut where the spans change.
    (Charging a whole gap to the span at its middle, the rule of
    ``trace.idle_gaps``, gave a 0.1 ms span the 3 ms gap it sat in.)"""
    pieces = innermost_pieces(spans, offset)
    out: list[tuple[float, ProgramSpan | None]] = []
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        i = j
        while i < len(pieces) and pieces[i][0] < b:
            seconds = min(b, pieces[i][1]) - max(a, pieces[i][0])
            if seconds > 0:
                out.append((seconds, pieces[i][2]))
                covered += seconds
            i += 1
        if b - a > covered:
            out.append((b - a - covered, None))
    return out


def idle_by_span(charged: list[tuple[float, ProgramSpan | None]]) -> dict[str, float]:
    """Idle seconds by what the program was doing (the innermost span)."""
    sums: dict[str, float] = {}
    for seconds, holder in charged:
        name = NO_SPAN if holder is None else holder.name
        sums[name] = sums.get(name, 0.0) + seconds
    return sums


def idle_inside(
    charged: list[tuple[float, ProgramSpan | None]], spans: list[ProgramSpan], name: str
) -> float:
    """Idle seconds while a span called ``name`` was open, its children's
    time included."""
    by_id = {s.span_id: s for s in spans}
    total = 0.0
    for seconds, holder in charged:
        while holder is not None and holder.name != name:
            holder = by_id.get(holder.parent_id)
        if holder is not None:
            total += seconds
    return total
