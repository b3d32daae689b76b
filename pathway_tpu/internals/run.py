"""pw.run / pw.run_all (reference: python/pathway/internals/run.py:12,
GraphRunner internals/graph_runner/__init__.py:36)."""

from __future__ import annotations

import threading
from typing import Any

from pathway_tpu.engine.runtime import Runtime
from pathway_tpu.internals import parse_graph


class MonitoringLevel:
    AUTO = "auto"
    AUTO_ALL = "auto_all"
    NONE = "none"
    IN_OUT = "in_out"
    ALL = "all"


# set by `python -m pathway_tpu.analysis`: the script's pw.run() calls
# declare the graph but never build a Runtime
_build_only = False


def run(
    *,
    debug: bool = False,
    monitoring_level: Any = MonitoringLevel.AUTO,
    with_http_server: bool = False,
    default_logging: bool = True,
    persistence_config: Any = None,
    runtime_typechecking: bool | None = None,
    license_key: str | None = None,
    terminate_on_error: bool = True,
    autocommit_duration_ms: int = 50,
    diagnostics: str | None = None,
    **kwargs: Any,
) -> None:
    """Execute the dataflow declared so far (all registered outputs).

    ``diagnostics`` runs the Graph Doctor (pathway_tpu.analysis) over the
    declared graph before the engine starts: ``"warn"`` logs findings,
    ``"error"`` raises GraphDoctorError on warning-or-worse findings so
    not a single batch executes, ``"off"``/None skips the pass.
    """
    if _build_only:
        return
    G = parse_graph.G
    seeds = list(G.outputs)
    if kwargs.pop("_all_nodes", False):
        from pathway_tpu.engine import nodes as _nodes

        seeds += _nodes.ALL_NODES
    if not seeds:
        return
    if diagnostics not in (None, "off"):
        from pathway_tpu.analysis import check_before_run

        check_before_run(seeds, diagnostics)
    # join the process group when `pathway spawn -n N` launched us
    # (reference env contract PATHWAY_PROCESSES/PROCESS_ID, config.rs:88).
    # The engine's multi-process transport is the host mesh (TCP, DCN
    # rung) — the Runtime joins it itself; the jax.distributed device
    # group is only needed for cross-process device collectives (sharded
    # KNN/embed) and is joined when PATHWAY_JAX_DISTRIBUTED=1.
    import os as _os

    from pathway_tpu.parallel.host_exchange import dcn_active

    if not dcn_active() or _os.environ.get("PATHWAY_JAX_DISTRIBUTED") == "1":
        from pathway_tpu.parallel.distributed import maybe_initialize

        maybe_initialize()
    else:
        import logging

        logging.getLogger("pathway_tpu").warning(
            "multi-process engine: host-row exchange active; cross-process "
            "DEVICE collectives (sharded KNN/embed over jax.distributed) "
            "are disabled — set PATHWAY_JAX_DISTRIBUTED=1 to join the "
            "device group as well"
        )
    from pathway_tpu.internals.compile_cache import configure_compile_cache

    configure_compile_cache()
    runtime = Runtime(seeds, autocommit_ms=autocommit_duration_ms)
    G.runtime = runtime
    G.last_runtime = runtime
    if persistence_config is None:
        # record/replay debugging via env (reference: PATHWAY_REPLAY_STORAGE,
        # internals/config.py:64-97 + `pathway spawn --record`)
        from pathway_tpu.internals.config import get_pathway_config

        pw_cfg = get_pathway_config()
        if pw_cfg.replay_storage:
            from pathway_tpu import persistence as _p

            persistence_config = _p.Config(
                backend=_p.Backend.filesystem(pw_cfg.replay_storage),
                snapshot_access=pw_cfg.snapshot_access or "record",
            )
    if persistence_config is not None:
        from pathway_tpu.persistence._runtime_glue import attach_persistence

        attach_persistence(runtime, persistence_config)
    if with_http_server or monitoring_level in (
        MonitoringLevel.ALL,
        MonitoringLevel.IN_OUT,
    ):
        try:
            from pathway_tpu.internals.monitoring_server import start_http_server

            start_http_server(runtime)
        except Exception:
            pass
    monitor = None
    import sys as _sys

    want_tui = monitoring_level in (MonitoringLevel.ALL, MonitoringLevel.IN_OUT) or (
        monitoring_level in (MonitoringLevel.AUTO, MonitoringLevel.AUTO_ALL)
        and _sys.stdout.isatty()
    )
    if want_tui:
        try:
            from pathway_tpu.internals.monitoring import StatsMonitor

            monitor = StatsMonitor(runtime)
            monitor.start()
        except Exception:
            monitor = None
    from pathway_tpu.internals.telemetry import get_telemetry

    from pathway_tpu.internals import errors as _errors

    err_pos = _errors.error_count()
    try:
        with get_telemetry().span(
            "pathway.run", nodes=len(runtime.order)
        ):
            runtime.run()
        if terminate_on_error:
            first = _errors.first_exception_since(err_pos)
            if first is not None:
                # surface the first runtime error with its original type
                # (reference: terminate_on_error=true run semantics,
                # python_api.rs:3329)
                if isinstance(first, BaseException):
                    raise first
                raise RuntimeError(first)
    finally:
        if monitor is not None:
            monitor.stop()
        G.runtime = None
        for hook in G.post_run_hooks:
            try:
                hook()
            except Exception:
                pass


def run_all(**kwargs: Any) -> None:
    """Execute the ENTIRE declared graph, including nodes with no
    registered output (reference: GraphRunner run_all vs run_outputs)."""
    run(_all_nodes=True, **kwargs)
