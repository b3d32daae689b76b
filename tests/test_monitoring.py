"""Monitoring endpoint + runtime stats (reference: src/engine/http_server.rs
OpenMetrics endpoint; ProberStats src/engine/graph.rs:533) and the Flight
Recorder (pathway_tpu/observability): registry semantics, histogram
quantiles, exposition-format conformance of the scraped `/metrics` body,
and the `/debug/*` surfaces."""

import json
import math
import socket
import threading
import urllib.error
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.debug import T, table_to_pandas


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_runtime_stats_counters():
    t = T(
        """
        v
        1
        2
        3
        """
    )
    res = t.groupby().reduce(total=pw.reducers.sum(t.v))
    table_to_pandas(res)
    rt = pw.internals.parse_graph.G.last_runtime
    assert rt is not None
    s = rt.stats
    assert s.ticks >= 1
    assert sum(s.rows_in.values()) >= 3
    snap = s.snapshot()
    assert snap["rows_in_total"] >= 3
    assert snap["ticks"] >= 1


def test_metrics_http_endpoint():
    from pathway_tpu.engine.nodes import InputNode
    from pathway_tpu.engine.runtime import Runtime, StaticSource
    from pathway_tpu.internals.monitoring_server import start_http_server

    class _Empty(StaticSource):
        def events(self):
            return iter(())

    node = InputNode(_Empty(["a"]), ["a"])
    rt = Runtime([node])
    rt.run_static()
    port = _free_port()
    server = start_http_server(rt, port=port)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as resp:
            body = resp.read().decode()
        assert "pathway_ticks_total" in body
        assert "pathway_logical_time" in body
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=5
        ) as resp:
            status = json.loads(resp.read().decode())
        assert status["ticks"] >= 1
    finally:
        server.shutdown()


def test_monitoring_server_reused_across_runs_without_pinning_runtime():
    """A second monitored run must re-attach to the existing server on
    the same port (no thread leak, no ephemeral-port fallback serving
    stale stats) and a finished run's graph must stay collectable — the
    handler holds the runtime weakly."""
    import gc
    import weakref

    from pathway_tpu.engine.nodes import InputNode
    from pathway_tpu.engine.runtime import Runtime, StaticSource
    from pathway_tpu.internals import monitoring_server as ms

    class _Empty(StaticSource):
        def events(self):
            return iter(())

    port = _free_port()
    rt1 = Runtime([InputNode(_Empty(["a"]), ["a"])])
    rt1.run_static()
    server = ms.start_http_server(rt1, port=port)
    try:
        rt2 = Runtime([InputNode(_Empty(["a"]), ["a"])])
        rt2.run_static()
        assert ms.start_http_server(rt2, port=port) is server
        ref = weakref.ref(rt1)
        del rt1
        gc.collect()
        assert ref() is None, "monitoring handler pinned a finished run"
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=5
        ) as resp:
            status = json.loads(resp.read().decode())
        assert status["ticks"] >= 1  # rt2's stats, served live
    finally:
        server.shutdown()
    # shutdown deregisters AND releases the socket: a fresh start must
    # bind the canonical port again, not fall back to ephemeral
    assert (ms._monitoring_host(), port) not in ms._servers
    fresh = ms.start_http_server(None, port=port)
    try:
        assert fresh is not server
        assert fresh.server_address[1] == port
    finally:
        fresh.shutdown()


def test_process_gauges_and_metrics_endpoint():
    """Process CPU/mem gauges (reference: telemetry.rs:359-416) surface on
    the Prometheus endpoint alongside operator latency and frontier lag."""
    from pathway_tpu.internals.telemetry import process_gauges

    g = process_gauges()
    assert g["process_cpu_seconds_total"] > 0
    assert g["process_memory_rss_bytes"] > 1024 * 1024  # at least 1 MiB

    import pathway_tpu as pw
    from pathway_tpu.internals.monitoring_server import _render_metrics

    class S(pw.Schema):
        v: int

    t = pw.debug.table_from_rows(S, [(1,), (2,)])
    res = t.reduce(s=pw.reducers.sum(t.v))
    pw.debug.table_to_dicts(res)
    rt = pw.internals.parse_graph.G.last_runtime
    body = _render_metrics(rt)
    assert "pathway_process_cpu_seconds_total" in body
    assert "pathway_process_memory_rss_bytes" in body
    assert "pathway_frontier_lag_ms" in body
    assert "pathway_operator_seconds_total" in body


# --- Flight Recorder: registry unit tests --------------------------------


def _registry():
    from pathway_tpu.observability import MetricsRegistry

    return MetricsRegistry()


def test_registry_counter_gauge_semantics():
    reg = _registry()
    c = reg.counter("x_total", "help")
    c.inc()
    c.inc(2.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", "help")
    g.set(5)
    g.dec(2)
    body = reg.render()
    assert "x_total 3.5" in body
    assert "\ng 3" in body
    # get-or-create is idempotent; a type/label mismatch is an error
    assert reg.counter("x_total", "help") is c
    with pytest.raises(ValueError):
        reg.gauge("x_total", "help")
    with pytest.raises(ValueError):
        reg.counter("x_total", "help", labelnames=("a",))


def test_registry_labels_and_escaping():
    from pathway_tpu.observability import parse_exposition

    reg = _registry()
    c = reg.counter("rows_total", "rows", labelnames=("table",))
    evil = 'my "table"\nwith\\escapes'
    c.labels(evil).inc(7)
    body = reg.render()
    assert "\n" not in body.split("rows_total{")[1].split("}")[0]
    families, errors = parse_exposition(body)
    assert errors == []
    (sample,) = families["rows_total"].samples
    # the parser must round-trip the exact original label value
    assert sample.labels["table"] == evil
    assert sample.value == 7


def test_registry_gauge_function_and_collectors():
    reg = _registry()
    reg.gauge("live", "fn-backed").set_function(lambda: 42.0)
    calls = []
    reg.register_collector(lambda: calls.append(1))

    def boom():
        raise RuntimeError("broken bridge")

    reg.register_collector(boom)  # must not take down the scrape
    body = reg.render()
    assert "live 42" in body
    assert calls == [1]


def test_histogram_buckets_and_quantiles():
    from pathway_tpu.observability import log_linear_buckets

    reg = _registry()
    h = reg.histogram("lat_seconds", "latency", buckets=log_linear_buckets())
    # 100 samples at ~1ms, 5 at ~100ms: p50 lands in the 1ms bucket,
    # p99 in the 100ms one. Log-linear bounds keep relative error small.
    for _ in range(100):
        h.observe(0.001)
    for _ in range(5):
        h.observe(0.1)
    p50 = h.quantile(0.5)
    p99 = h.quantile(0.99)
    assert 0.0005 < p50 < 0.002, p50
    assert 0.05 < p99 < 0.2, p99
    assert h.quantile(0.0) <= p50 <= p99 <= h.quantile(1.0)
    empty = reg.histogram("empty_seconds", "no samples")
    assert math.isnan(empty.quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_exposition_shape():
    from pathway_tpu.observability import validate_exposition

    reg = _registry()
    h = reg.histogram(
        "req_seconds", "latency", labelnames=("route",),
        buckets=(0.1, 1.0, 10.0),
    )
    h.labels("/v1/retrieve").observe(0.05)
    h.labels("/v1/retrieve").observe(5.0)
    body = reg.render()
    assert 'req_seconds_bucket{route="/v1/retrieve",le="0.1"} 1' in body
    assert 'req_seconds_bucket{route="/v1/retrieve",le="+Inf"} 2' in body
    assert 'req_seconds_count{route="/v1/retrieve"} 2' in body
    assert validate_exposition(body) == []


def test_registry_histogram_bucket_mismatch_raises():
    reg = _registry()
    h = reg.histogram("h_seconds", "x", buckets=(1.0, 2.0))
    # omitting buckets means "whatever is registered"
    assert reg.histogram("h_seconds", "x") is h
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", "x", buckets=(5.0,))


def test_build_info_placeholder_retired_after_backend_init(monkeypatch):
    from pathway_tpu.observability import jax_metrics

    reg = _registry()
    monkeypatch.setattr(
        jax_metrics, "_backend_if_initialized", lambda: None
    )
    jax_metrics._install_build_info(reg)
    assert 'platform="uninitialized"' in reg.render()

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v4"

    monkeypatch.setattr(
        jax_metrics, "_backend_if_initialized", lambda: [FakeDevice()]
    )
    body = reg.render()
    # exactly ONE build_info series, and it is the resolved one
    assert "uninitialized" not in body
    lines = [
        l for l in body.splitlines() if l.startswith("pathway_build_info{")
    ]
    assert len(lines) == 1 and 'platform="tpu"' in lines[0], lines
    # the Pallas mode resolves with the backend (the REAL one: the cpu)
    assert 'pallas="interpret"' in lines[0], lines


def test_log_linear_buckets_monotone():
    from pathway_tpu.observability import log_linear_buckets

    bounds = log_linear_buckets()
    assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
    assert bounds[0] <= 2e-4  # resolves sub-ms device top-k
    assert bounds[-1] >= 60.0  # and a minute-long cold compile


# --- exposition-format validator -----------------------------------------


def test_validator_catches_violations():
    from pathway_tpu.observability import validate_exposition

    assert validate_exposition(
        "# TYPE a counter\n# TYPE a counter\na_total 1\n"
    )  # duplicate TYPE
    assert validate_exposition("# TYPE b counter\nb 1\n")  # no _total
    assert validate_exposition(
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\nh_bucket{le="+Inf"} 5\n'
        "h_sum 1\nh_count 5\n"
    )  # non-monotone buckets
    assert validate_exposition(
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 1\nh_sum 1\nh_count 1\n'
    )  # missing +Inf
    assert validate_exposition("x{bad 1\n")  # malformed sample
    assert validate_exposition("x 1\nx 2\n")  # duplicate sample
    assert validate_exposition("ok_total 1\nother 2.5e-3\n") == []


# --- end-to-end: scrape a live run ---------------------------------------


def _scrape(port: int, path: str = "/metrics") -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.read().decode()


def test_scraped_metrics_pass_validator_with_knn_and_tick_histograms():
    """Acceptance: a scrape during a run exposes _bucket/_sum/_count for
    KNN query latency AND per-operator tick time, and the whole body
    passes the exposition validator."""
    import numpy as np

    from pathway_tpu.debug import table_to_dicts
    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.observability import validate_exposition
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnn

    class VS(pw.Schema):
        name: str
        vec: np.ndarray

    docs = pw.debug.table_from_rows(
        VS,
        [("a", np.array([1.0, 0.0])), ("b", np.array([0.0, 1.0]))],
    )
    queries = pw.debug.table_from_rows(
        VS, [("q", np.array([1.0, 0.1]))]
    )
    index = DataIndex(docs, TpuKnn(docs.vec, dimensions=2))
    result = index.query_as_of_now(
        queries.vec, number_of_matches=1
    ).select(qname=pw.left.name, names=pw.right.name)
    table_to_dicts(result)

    rt = pw.internals.parse_graph.G.last_runtime
    server = start_http_server(rt, port=_free_port())
    try:
        body = _scrape(server.server_address[1])
    finally:
        server.shutdown()
    for fam in ("pathway_knn_query_seconds", "pathway_operator_tick_seconds"):
        for suffix in ("_bucket", "_sum", "_count"):
            assert f"{fam}{suffix}" in body, f"{fam}{suffix} missing"
    assert "pathway_knn_queries_total" in body
    assert "pathway_build_info" in body
    violations = validate_exposition(body)
    assert violations == [], violations


def test_debug_threads_endpoint_lists_every_live_thread():
    from pathway_tpu.internals.monitoring_server import start_http_server

    ready = threading.Event()
    done = threading.Event()

    def parked():
        ready.set()
        done.wait(30)

    t = threading.Thread(target=parked, name="flight-recorder-probe")
    t.start()
    ready.wait(5)
    server = start_http_server(None, port=_free_port())
    try:
        dump = _scrape(server.server_address[1], "/debug/threads")
    finally:
        done.set()
        server.shutdown()
        t.join(5)
    for thread in threading.enumerate():
        if thread.ident is not None and thread is not t:
            assert f"ident={thread.ident}" in dump
    assert "'flight-recorder-probe'" in dump
    assert "in parked" in dump  # the dump shows WHERE it is parked


def test_debug_graph_endpoint():
    from pathway_tpu.internals.monitoring_server import start_http_server

    t = T(
        """
        v
        1
        2
        """
    )
    res = t.groupby().reduce(total=pw.reducers.sum(t.v))
    table_to_pandas(res)
    rt = pw.internals.parse_graph.G.last_runtime
    server = start_http_server(rt, port=_free_port())
    try:
        rows = json.loads(_scrape(server.server_address[1], "/debug/graph"))
    finally:
        server.shutdown()
    assert len(rows) == len(rt.order)
    for row in rows:
        assert {"id", "name", "type", "rows", "ns", "backlog"} <= set(row)
    # standalone mode (no runtime) serves an empty table, not a 500
    server = start_http_server(None, port=_free_port())
    try:
        assert json.loads(
            _scrape(server.server_address[1], "/debug/graph")
        ) == []
    finally:
        server.shutdown()


def test_debug_profile_501_when_profiler_unavailable(monkeypatch):
    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.observability import debug as obs_debug

    monkeypatch.setattr(obs_debug, "_get_profiler", lambda: None)
    server = start_http_server(None, port=_free_port())
    port = server.server_address[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _scrape(port, "/debug/profile?seconds=0.1")
        assert exc_info.value.code == 501
        # bad duration is a 400 regardless of profiler availability
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _scrape(port, "/debug/profile?seconds=abc")
        assert exc_info.value.code == 400
    finally:
        server.shutdown()


def test_debug_profile_writes_trace_when_available():
    import os

    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.observability.debug import _get_profiler

    if _get_profiler() is None:
        pytest.skip("jax profiler unavailable in this environment")
    server = start_http_server(None, port=_free_port())
    try:
        out = json.loads(
            _scrape(server.server_address[1], "/debug/profile?seconds=0.1")
        )
    finally:
        server.shutdown()
    assert os.path.isdir(out["trace_dir"])


# --- monitoring server bind host / port fallback -------------------------


def test_monitoring_host_env(monkeypatch):
    from pathway_tpu.internals import monitoring_server

    monkeypatch.setenv("PATHWAY_MONITORING_HOST", "0.0.0.0")
    assert monitoring_server._monitoring_host() == "0.0.0.0"
    monkeypatch.delenv("PATHWAY_MONITORING_HOST")
    assert monitoring_server._monitoring_host() == "127.0.0.1"


def test_port_conflict_falls_back_to_ephemeral(caplog):
    """A port held by a FOREIGN process falls back to ephemeral with a
    warning; this process's own server on that port is reused instead
    (no per-run server leak)."""
    import logging

    from pathway_tpu.internals.monitoring_server import start_http_server

    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    taken = blocker.getsockname()[1]
    try:
        with caplog.at_level(logging.WARNING, logger="pathway_tpu"):
            second = start_http_server(None, port=taken)
        try:
            actual = second.server_address[1]
            assert actual != taken
            assert any(
                "ephemeral" in rec.message for rec in caplog.records
            )
            assert "pathway_build_info" in _scrape(actual)
            # same requested port from THIS process: reuse, not another
            # fallback server
            assert start_http_server(None, port=taken) is second
        finally:
            second.shutdown()
    finally:
        blocker.close()
