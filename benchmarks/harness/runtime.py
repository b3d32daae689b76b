"""One run of one cell: the device it runs on, its window, its result line."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from benchmarks.harness import trace as tracing
from benchmarks.harness.loader import ROOT, Cell, load_metric_reader
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.spans import Spans

TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # git-ignored, removed after reading


class NoChip(SystemExit):
    """The cell's chips are not there: no result is printed."""


def find_devices(chips: int, rehearse: bool) -> dict:
    """The device dict of the result line. Off the TPU only a rehearsal runs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise NoChip("--rehearse runs on the CPU only")
    elif platform != "tpu":
        print(f"no TPU: jax.devices()[0].platform is {platform!r}", file=sys.stderr)
        raise NoChip(3)
    elif len(devices) < chips:
        print(f"the cell needs {chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        raise NoChip(3)
    used = devices[:chips]
    return {"platform": platform, "kind": used[0].device_kind, "count": len(used)}


def prepare(workload: str, rehearse: bool) -> tuple[Cell, dict]:
    """What ``run.py`` and ``study.py`` do before anything is built: the
    environment, the cell's files, the compile cache, the look for the chips."""
    from benchmarks.harness.loader import load_cell

    os.environ.setdefault("HF_HUB_OFFLINE", "1")  # nothing is fetched
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    cell = load_cell(workload, rehearse=rehearse)
    if not rehearse:  # a rehearsal's programs are toys: nothing worth keeping
        from benchmarks.harness.sut import configure_compile_cache

        configure_compile_cache()
    return cell, find_devices(cell.chips, rehearse)


def memory_peak_bytes(chips: int):
    import jax

    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts the programs JAX builds, so that a window can show it built
    none. The event wraps ``compile_or_get_cached`` (jax 0.9.0, pxla.py), so
    a program loaded from the persistent cache counts like one compiled:
    ``tests/test_runtime.py`` holds it to that."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _seconds: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


@dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    rehearse: bool
    device: dict
    spans: Spans = field(default_factory=Spans)
    ticks: list[dict] = field(default_factory=list)  # work per finished tick
    window_start: float = 0.0
    window_end: float = 0.0
    scope_end: float | None = None  # where the traced part of the window ended
    scope_ticks: int | None = None
    phases: list[tuple[str, float]] = field(default_factory=list)  # set-up, by phase
    _annotation: object = None
    _phase_start: float = field(default_factory=time.perf_counter)  # or process start

    def phase(self, name: str) -> None:
        """Close a phase of set-up: its seconds go to standard error."""
        now = time.perf_counter()
        self.phases.append((name, now - self._phase_start))
        print(f"setup {name}: {now - self._phase_start:.3f} s", file=sys.stderr)
        self._phase_start = now

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def peaks(self) -> dict | None:
        """The chip's peaks; a rehearsal has none and reports no share."""
        return None if self.rehearse else peaks_for(self.device["kind"])

    # -- the window ---------------------------------------------------------
    def open_window(self) -> None:
        import jax

        if self.traced:
            tracing.start(TRACE_DIR)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        self.window_start = time.perf_counter()

    def tick_done(self, work: dict) -> bool:
        """Record a finished tick's work; True once the window has run out."""
        now = time.perf_counter()
        self.ticks.append(work)
        self.window_end = now
        if self._annotation is not None and (
            now - self.window_start >= float(self.traffic["trace_seconds"])
            or now - self.window_start >= self.seconds
        ):
            self._close_scope(now)
        return now - self.window_start >= self.seconds

    def _close_scope(self, now: float) -> None:
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        self.scope_end, self.scope_ticks = now, len(self.ticks)
        tracing.stop()

    def close_window(self) -> None:
        if self._annotation is not None:
            self._close_scope(time.perf_counter())

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    # -- per-layer metrics ---------------------------------------------------
    def reduce_context(self) -> "ReduceContext":
        trace = tracing.read(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return ReduceContext(
            spans=self.spans,
            ticks=self.ticks[: self.scope_ticks],
            seconds=self.scope_end - self.window_start,
            trace=trace,
            peaks=self.peaks,
            chips=self.cell.chips,
            config=self.config,
        )


@dataclass
class ReduceContext:
    """What a per-layer metric's reader is given: the traced part of the
    window. ``ticks`` are its finished ticks' work records, ``spans`` the
    host spans (of the whole run; ``scope`` picks the traced ticks)."""

    spans: Spans
    ticks: list[dict]
    seconds: float
    trace: tracing.Trace
    peaks: dict | None
    chips: int
    config: dict

    def per_tick_ms(self, names: list[str]) -> list[float]:
        return self.spans.per_tick_ms(names)[: len(self.ticks)]


def per_layer_metrics(run: Run, context: ReduceContext) -> dict:
    """Each of the cell's per-layer metrics through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in run.cell.per_layer:
        reduce, args = load_metric_reader(metric["name"])
        value = reduce(context, **args)
        if value is None:
            continue
        extra = {}
        if isinstance(value, tuple):
            value, extra = value
        out[metric["name"]] = {"value": value, "unit": metric["unit"], **extra}
    return out


def device_section(run: Run, context: ReduceContext | None, peak_bytes) -> dict:
    device = dict(run.device)
    device["memory_peak_bytes"] = peak_bytes
    if context is not None:
        window = context.trace.window
        busy = tracing.mean_busy(context.trace, None if window is None else [window])
        if busy is not None and window is not None:
            device["busy_s"] = busy
            device["window_s"] = window[1] - window[0]
    if context is not None and context.trace.clock_offsets:
        # how the device's stamps were laid onto the host's spans (trace.py)
        device["clock_offset_ms"] = {d: s * 1e3 for d, s in context.trace.clock_offsets.items()}
        device["runs_inside_spans"] = dict(context.trace.contained)
    return device


def breakdown(context: ReduceContext) -> dict:
    window = context.trace.window
    return {
        "device_ops": tracing.top_device_ops(context.trace, window),
        "idle_gaps": tracing.idle_gaps(context.trace, window),
    }


def print_result(result: dict, compared: dict) -> None:
    """The compared numbers as the last lines of standard error, the result
    as the last line of standard output with ``compared`` as its last key."""
    sys.stdout.flush()
    for name, entry in compared.items():
        verdict = "ok" if entry["value"] <= entry["limit"] else "OVER"
        print(
            f"compared {name}: value {entry['value']!r} limit {entry['limit']!r} {verdict}",
            file=sys.stderr,
        )
    sys.stderr.flush()
    result = dict(result)
    result["compared"] = compared
    print(json.dumps(result))
    sys.stdout.flush()
