"""TPU/JAX gauges for the Flight Recorder.

Bridges ``jax.monitoring`` (compile events emitted by jit/pjit) and
per-device memory stats onto the metrics registry, plus a
``pathway_build_info`` info-style metric carrying platform/backend
labels. Everything here is defensive: the gauges must never *initialize*
a backend (a scrape is not the place to claim the chip), and must
degrade to absent series when jax or a given hook is unavailable.
"""

from __future__ import annotations

import threading
from typing import Any

from pathway_tpu.observability.registry import (
    REGISTRY,
    MetricsRegistry,
    sanitize_metric_name,
)
from pathway_tpu.observability.tracing import get_tracer

_install_lock = threading.Lock()
_installed_on: set[int] = set()


def _backend_if_initialized() -> Any | None:
    """The already-initialized default jax backend, or None. Never
    triggers backend initialization itself."""
    try:
        from jax._src import xla_bridge

        backends = getattr(xla_bridge, "_backends", None)
        if not backends:
            return None
        import jax

        return jax.local_devices()
    except Exception:
        return None


def install_jax_metrics(registry: MetricsRegistry | None = None) -> None:
    """Idempotent per registry; safe to call without jax installed."""
    registry = registry or REGISTRY
    with _install_lock:
        if id(registry) in _installed_on:
            return
        _installed_on.add(id(registry))

    _install_build_info(registry)
    _install_compile_hooks(registry)
    _install_device_memory(registry)


def _install_build_info(registry: MetricsRegistry) -> None:
    import platform as _platform

    try:
        from pathway_tpu import __version__ as pw_version
    except Exception:
        pw_version = "unknown"
    try:
        import jax

        jax_version = getattr(jax, "__version__", "unknown")
    except Exception:
        jax_version = "absent"

    info = registry.gauge(
        "pathway_build_info",
        "constant 1; build/runtime identity in labels (platform/backend/"
        "pallas resolve once jax initializes — scraping never forces "
        "init; pallas is compiled|interpret, see ops/backend.py)",
        labelnames=(
            "version", "python", "jax", "platform", "backend", "pallas"
        ),
    )
    state = dict.fromkeys(("platform", "backend", "pallas"), "uninitialized")

    def _labels() -> tuple:
        return (
            pw_version,
            _platform.python_version(),
            jax_version,
            state["platform"],
            state["backend"],
            state["pallas"],
        )

    def _collect() -> None:
        if state["platform"] == "uninitialized":
            devices = _backend_if_initialized()
            if devices:
                # retire the placeholder series, or a scrape that raced
                # backend init would expose two build_info identities
                info.remove(*_labels())
                state["platform"] = devices[0].platform
                state["backend"] = getattr(
                    devices[0], "device_kind", devices[0].platform
                )
                from pathway_tpu.ops.backend import pallas_mode

                state["pallas"] = pallas_mode()
        info.labels(*_labels()).set(1)

    registry.register_collector(_collect)


def _install_compile_hooks(registry: MetricsRegistry) -> None:
    """jit compile count/seconds via jax.monitoring listeners. jax emits
    duration events for tracing/compilation (event names vary by
    version); we keep a per-event breakdown plus a compile rollup."""
    try:
        import jax.monitoring as jmon
    except Exception:
        return
    events_total = registry.counter(
        "pathway_jax_events_total",
        "jax.monitoring events observed, by event key",
        labelnames=("event",),
    )
    durations_total = registry.counter(
        "pathway_jax_event_duration_seconds_total",
        "cumulative seconds of jax.monitoring duration events, by event key",
        labelnames=("event",),
    )
    compile_count = registry.counter(
        "pathway_jax_compilations_total",
        "jit/pjit compilations observed via jax.monitoring",
    )
    compile_seconds = registry.counter(
        "pathway_jax_compile_seconds_total",
        "cumulative seconds spent in jit/pjit compilation",
    )

    def _is_compile(event: str) -> bool:
        e = event.lower()
        return "compil" in e or "backend_compile" in e

    def on_event(event: str, **kwargs: Any) -> None:
        try:
            events_total.labels(sanitize_metric_name(event)).inc()
        except Exception:
            pass

    def on_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
        try:
            key = sanitize_metric_name(event)
            events_total.labels(key).inc()
            durations_total.labels(key).inc(max(0.0, float(duration_secs)))
            if _is_compile(event):
                compile_count.inc()
                compile_seconds.inc(max(0.0, float(duration_secs)))
            if "backend_compile" in event:
                # Trace Weaver: one record per program built or loaded
                # from the persistent cache (jax times every nested
                # jaxpr trace too: those would bury it). The listener
                # runs on the thread that compiled, so the record lands
                # under the span open there, and /debug/trace and the
                # slow-query log say which step compiled.
                get_tracer().record_finished(
                    "jax.compile",
                    int(max(0.0, float(duration_secs)) * 1e9),
                    event=key,
                )
        except Exception:
            pass

    try:
        jmon.register_event_listener(on_event)
        jmon.register_event_duration_secs_listener(on_duration)
    except Exception:
        pass


def _install_device_memory(registry: MetricsRegistry) -> None:
    mem = registry.gauge(
        "pathway_device_memory_bytes",
        "per-device memory stats from device.memory_stats() (absent until "
        "the backend initializes; CPU backends report no stats)",
        labelnames=("device", "kind"),
    )
    ndev = registry.gauge(
        "pathway_jax_local_devices",
        "local jax device count (0 until the backend initializes)",
    )

    def _collect() -> None:
        devices = _backend_if_initialized()
        ndev.set(len(devices) if devices else 0)
        if not devices:
            return
        for d in devices:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            dev_label = f"{d.platform}:{d.id}"
            for kind in (
                "bytes_in_use",
                "peak_bytes_in_use",
                "bytes_limit",
                "largest_free_block_bytes",
            ):
                if kind in stats:
                    mem.labels(dev_label, kind).set(float(stats[kind]))

    registry.register_collector(_collect)
