"""On-demand debugging surfaces: thread dumps, graph tables, profiling.

``thread_stack_dump`` answers *where* a live process is stuck: a GET
/debug/threads returns every thread's stack in one request.
``take_profile`` wraps ``jax.profiler``
trace capture (guarded — callers surface 501 when unavailable instead of
crashing the serving process).
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Any


def thread_stack_dump() -> str:
    """Human-readable stack of every live Python thread."""
    frames = sys._current_frames()
    by_ident = {t.ident: t for t in threading.enumerate()}
    out: list[str] = [
        f"=== thread dump: {len(frames)} thread(s), "
        f"pid={__import__('os').getpid()} ===",
    ]
    for ident, frame in sorted(frames.items(), key=lambda kv: kv[0] or 0):
        t = by_ident.get(ident)
        name = t.name if t is not None else "<unknown>"
        daemon = " daemon" if t is not None and t.daemon else ""
        out.append(f"\n--- Thread {name!r} (ident={ident}{daemon}) ---")
        out.extend(
            line.rstrip("\n")
            for line in traceback.format_stack(frame)
        )
    return "\n".join(out) + "\n"


def graph_table(runtime: Any) -> list[dict]:
    """Per-node rows/ns/backlog rows for /debug/graph — the JSON twin of
    the TUI operator table (internals/monitoring.py)."""
    if runtime is None:
        return []
    from pathway_tpu.engine.nodes import InputNode
    from pathway_tpu.engine.runtime import StreamingSource

    stats = runtime.stats
    plan = getattr(runtime, "compiled_plan", None)
    seg_of: dict[int, object] = {}
    if plan is not None:
        for seg in plan.segments:
            for n in seg.nodes:
                seg_of[n.id] = seg
    rows = []
    for node in runtime.order:
        backlog = 0
        if isinstance(node, InputNode) and isinstance(
            getattr(node, "source", None), StreamingSource
        ):
            session = node.source.session
            with session._lock:
                backlog = len(session._rows) + len(session._upserts)
        row = {
            "id": node.id,
            "name": f"{node.name}_{node.id}",
            "type": type(node).__name__,
            "rows": stats.node_rows.get(node.id, 0),
            "ns": stats.node_ns.get(node.id, 0),
            "rows_in": stats.rows_in.get(node.id, 0),
            "rows_out": stats.rows_out.get(node.id, 0),
            "backlog": backlog,
        }
        # Tick Forge: which fused segment (if any) this node rides, and
        # how often the segment actually dispatched compiled vs fell
        # back to the interpreter (tail carries the counters; member
        # rows/ns are attributed to the tail)
        seg = seg_of.get(node.id)
        row["compiled"] = seg is not None
        if seg is not None:
            row["segment"] = seg.seg_id
            if node.id == seg.tail.id:
                row["segment_tail"] = True
                row["compiled_ticks"] = seg.compiled_ticks
                row["fallback_ticks"] = seg.fallback_ticks
        rows.append(row)
    return rows


class ProfilerUnavailable(RuntimeError):
    """jax (or its profiler) is not importable / not functional here."""


def _get_profiler() -> Any | None:
    try:
        import jax.profiler as profiler

        if hasattr(profiler, "start_trace") and hasattr(
            profiler, "stop_trace"
        ):
            return profiler
    except Exception:
        pass
    return None


_profile_lock = threading.Lock()


def take_profile(seconds: float, logdir: str | None = None) -> str:
    """Capture a jax profiler trace for `seconds`; returns the trace
    directory. Raises ProfilerUnavailable when jax/profiler is absent and
    ValueError on a bad duration. Serialized — concurrent requests would
    fight over the single global profiler session."""
    seconds = float(seconds)
    if not 0.0 < seconds <= 120.0:
        raise ValueError("seconds must be in (0, 120]")
    profiler = _get_profiler()
    if profiler is None:
        raise ProfilerUnavailable(
            "jax.profiler is unavailable in this process"
        )
    if logdir is None:
        import tempfile

        logdir = tempfile.mkdtemp(prefix="pathway_profile_")
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already in progress")
    try:
        profiler.start_trace(logdir)
        try:
            time.sleep(seconds)
        finally:
            profiler.stop_trace()
    finally:
        _profile_lock.release()
    return logdir
