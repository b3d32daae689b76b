"""Trace Weaver (pathway_tpu/observability/tracing.py): W3C traceparent
contract, span ring semantics, the Chrome trace-event validator, the
slow-query log, thread-safe Telemetry timings, and the end-to-end
acceptance paths — a REST request yields one stitched root→embed→KNN
span tree, and a 2-process host-mesh run carries the same trace id
across the wire (frames stamp a traceparent; the lockstep barrier agrees
on one tick trace group-wide)."""

import json
import logging
import socket
import textwrap
import threading
import time
import urllib.error
import urllib.request

import pytest

import pathway_tpu as pw
from pathway_tpu.observability import tracing

FIXED_TRACE = "ab" * 16
FIXED_SPAN = "cd" * 8
FIXED_TRACEPARENT = f"00-{FIXED_TRACE}-{FIXED_SPAN}-01"


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracer = tracing.get_tracer()
    tracer.clear()
    saved_slow = tracer.slow_ms
    saved_enabled = tracer.enabled
    with tracing._pending_lock:
        tracing._pending.clear()
    yield
    tracer.clear()
    tracer.slow_ms = saved_slow
    tracer.enabled = saved_enabled
    with tracing._pending_lock:
        tracing._pending.clear()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# --- traceparent contract -------------------------------------------------


def test_traceparent_generate_parse_roundtrip():
    tracer = tracing.Tracer(capacity=16)
    with tracer.span("root") as sp:
        tp = sp.context.traceparent()
    ctx = tracing.parse_traceparent(tp)
    assert ctx is not None
    assert ctx.trace_id == sp.context.trace_id
    assert ctx.span_id == sp.context.span_id
    assert ctx.flags == 1
    # parse accepts uppercase-ish whitespace-padded input, case-folded
    assert tracing.parse_traceparent("  " + tp.upper() + " ") == ctx


@pytest.mark.parametrize(
    "header",
    [
        None,
        1234,
        "",
        "not-a-traceparent",
        "00-" + "ab" * 16 + "-" + "cd" * 8,  # missing flags
        "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # all-zero trace id
        "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero span id
        "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # forbidden version
        "00-" + "ab" * 15 + "-" + "cd" * 8 + "-01",  # short trace id
        "00-" + "xy" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
    ],
)
def test_traceparent_malformed_headers_rejected(header):
    assert tracing.parse_traceparent(header) is None


def test_span_parent_child_links_and_explicit_parent():
    tracer = tracing.Tracer(capacity=64)
    remote = tracing.parse_traceparent(FIXED_TRACEPARENT)
    with tracer.span("ingress", parent=remote, root=True) as root:
        assert root.trace_id == FIXED_TRACE
        with tracer.span("inner") as child:
            assert child.trace_id == FIXED_TRACE
            # a root=True span breaks out of the ambient trace
            with tracer.span("fresh", root=True) as fresh:
                assert fresh.trace_id != FIXED_TRACE
    recs = {r.name: r for r in tracer.spans()}
    assert recs["ingress"].parent_id == FIXED_SPAN
    assert recs["inner"].parent_id == recs["ingress"].span_id
    assert recs["fresh"].parent_id is None


def test_ring_buffer_is_bounded():
    tracer = tracing.Tracer(capacity=10)
    for i in range(50):
        with tracer.span(f"s{i}"):
            pass
    recs = tracer.spans()
    assert len(recs) == 10
    assert recs[-1].name == "s49"  # newest kept, oldest evicted


def test_disabled_tracer_is_noop():
    tracer = tracing.Tracer(capacity=16, enabled=False)
    before = tracing.current_context()
    with tracer.span("x") as sp:
        assert sp is tracing.NOOP_SPAN
        assert sp.trace_id is None
        sp.set_attribute("k", "v")  # must not raise
        assert tracing.current_context() is before
    assert tracer.spans() == []


def test_pending_request_registry():
    ctx = tracing.parse_traceparent(FIXED_TRACEPARENT)
    tracing.register_pending(1, ctx)
    tracing.register_pending(2, tracing.SpanContext("ef" * 16, "12" * 8))
    # oldest pending wins; unregistering it promotes the next
    assert tracing.pending_context() == ctx
    assert tracing.pending_traceparent() == ctx.traceparent()
    tracing.unregister_pending(1)
    assert tracing.pending_context().trace_id == "ef" * 16
    tracing.unregister_pending(2)
    assert tracing.pending_context() is None
    tracing.register_pending(3, None)  # None context is ignored
    assert tracing.pending_context() is None


# --- Chrome trace-event export + validator --------------------------------


def test_chrome_trace_export_validates_and_links_spans():
    tracer = tracing.Tracer(capacity=64)
    with tracer.span("outer", route="/x"):
        with tracer.span("inner"):
            pass
    doc = tracer.chrome_trace()
    assert tracing.validate_chrome_trace(doc) == []
    events = {
        e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"
    }
    assert events["inner"]["args"]["parent_id"] == (
        events["outer"]["args"]["span_id"]
    )
    assert events["outer"]["args"]["route"] == "/x"
    assert events["outer"]["dur"] >= events["inner"]["dur"]
    # round-trips through JSON (what /debug/trace serves)
    assert tracing.validate_chrome_trace(json.loads(json.dumps(doc))) == []


def test_chrome_trace_validator_catches_violations():
    v = tracing.validate_chrome_trace
    assert v({"traceEvents": "nope"})
    assert v("nope")
    assert v({"traceEvents": [{"ph": "Z", "name": "x"}]})  # unknown phase
    assert v({"traceEvents": [["not", "an", "object"]]})
    assert v(
        {"traceEvents": [{"ph": "X", "name": "", "pid": 1, "tid": 1,
                          "ts": 0, "dur": 1}]}
    )  # empty name
    assert v(
        {"traceEvents": [{"ph": "X", "name": "x", "pid": "p", "tid": 1,
                          "ts": 0, "dur": 1}]}
    )  # non-int pid
    assert v(
        {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                          "ts": -5, "dur": 1}]}
    )  # negative ts
    assert v(
        {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                          "ts": 0}]}
    )  # X without dur
    assert v(
        {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                          "ts": 0, "dur": 1, "args": "no"}]}
    )  # args not an object
    ok = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1, "tid": 1,
                           "ts": 0.5, "dur": 1.5, "args": {"a": 1}}]}
    assert v(ok) == []
    assert v(ok["traceEvents"]) == []  # bare-array form


def test_spans_trailing_window_filter():
    tracer = tracing.Tracer(capacity=64)
    with tracer.span("old"):
        pass
    assert [r.name for r in tracer.spans(seconds=60)] == ["old"]
    assert tracer.spans(seconds=1e-9) == []


# --- slow-query log -------------------------------------------------------


def test_slow_query_log_dumps_child_breakdown(caplog):
    tracer = tracing.Tracer(capacity=64)
    tracer.slow_ms = 1.0
    with caplog.at_level(logging.WARNING, logger="pathway_tpu"):
        with tracer.span("http.request") as root:
            with tracer.span("knn.search"):
                time.sleep(0.005)
    msgs = [r.message for r in caplog.records if "slow trace" in r.message]
    assert msgs, "slow root span did not log"
    assert root.trace_id in msgs[0]
    assert "knn.search" in msgs[0]  # full child breakdown rides along
    # an ingress span that JOINED a caller's trace (non-None parent_id)
    # is still slow-log eligible — it is this process's local root
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="pathway_tpu"):
        with tracer.span(
            "http.request",
            parent=tracing.parse_traceparent(FIXED_TRACEPARENT),
            root=True,
            ingress=True,
        ):
            time.sleep(0.005)
    assert any(
        "slow trace" in r.message and FIXED_TRACE in r.message
        for r in caplog.records
    ), "slow ingress span did not log"
    # fast root spans below the threshold stay quiet
    caplog.clear()
    tracer.slow_ms = 10_000.0
    with caplog.at_level(logging.WARNING, logger="pathway_tpu"):
        with tracer.span("http.request"):
            pass
    assert not [
        r for r in caplog.records if "slow trace" in r.message
    ]


def test_trace_tree_default_selects_joined_trace():
    """pw.debug.trace_tree() with no trace id picks the most recent LOCAL
    root — including a request that joined a caller's trace (its parent
    span id lives outside the ring), not just parentless spans."""
    tracer = tracing.get_tracer()
    with tracer.span("engine.tick"):  # older, unrelated fresh-root trace
        pass
    with tracer.span(
        "http.request",
        parent=tracing.parse_traceparent(FIXED_TRACEPARENT),
        root=True,
        ingress=True,
    ):
        with tracer.span("knn.search"):
            pass
    tree = pw.debug.trace_tree()
    assert "http.request" in tree and "knn.search" in tree, tree


# --- Telemetry absorption -------------------------------------------------


def test_telemetry_span_records_into_tracer():
    from pathway_tpu.internals.telemetry import Telemetry

    tel = Telemetry()
    tracer = tracing.get_tracer()
    with tel.span("pathway.run", nodes=3):
        inner_tp = tel.trace_parent()
    assert inner_tp is not None
    ctx = tracing.parse_traceparent(inner_tp)
    assert ctx is not None
    recs = [r for r in tracer.spans() if r.name == "pathway.run"]
    assert recs and recs[-1].trace_id == ctx.trace_id
    assert recs[-1].attributes["nodes"] == 3
    assert tel.timings["pathway.run"] > 0


def test_telemetry_timings_accumulation_is_thread_safe():
    from pathway_tpu.internals.telemetry import Telemetry

    tel = Telemetry()
    n_threads, n_iter = 8, 5000

    def hammer():
        for _ in range(n_iter):
            tel._add_timing("k", 1.0)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # 1.0 sums exactly in binary; a dropped read-modify-write shows up as
    # a short total (the pre-lock failure mode under the worker pool)
    assert tel.timings["k"] == float(n_threads * n_iter)


def test_sdk_provider_detection_is_shared_and_inactive_here():
    from pathway_tpu.internals import telemetry as tel_mod

    # one helper: the metrics gate delegates to the tracer module's
    # detection (no SDK in this image, so both read False)
    assert tel_mod._sdk_provider_active() is False
    assert tracing.otel_sdk_provider_active("metrics") is False
    assert tracing.otel_sdk_provider_active("trace") is False
    assert tel_mod._OtelMetrics().enabled is False


# --- histogram exemplars --------------------------------------------------


def test_histogram_exemplars_link_metrics_to_traces():
    from pathway_tpu.observability import MetricsRegistry, validate_exposition

    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "x", labelnames=("route",))
    h.labels("/a").observe(0.25, exemplar="t1" * 16)
    h.labels("/a").observe(0.5)  # no exemplar: previous one sticks
    (ex,) = reg.exemplars()
    assert ex["metric"] == "lat_seconds"
    assert ex["labels"] == {"route": "/a"}
    assert ex["trace_id"] == "t1" * 16
    assert ex["value"] == 0.25
    # the 0.0.4 text exposition has no exemplar syntax: output unchanged
    assert validate_exposition(reg.render()) == []
    assert "t1t1" not in reg.render()


# --- end-to-end: REST request → stitched trace ----------------------------


def _post_retrieve(port: int, payload: dict, traceparent: str):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/retrieve",
        data=json.dumps(payload).encode(),
        headers={
            "Content-Type": "application/json",
            "traceparent": traceparent,
        },
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read().decode()), dict(resp.headers)


def test_rest_request_yields_one_stitched_trace():
    """Acceptance: one REST query produces root (HTTP), embedder,
    KNN/index, and operator-tick spans sharing a single trace id,
    retrievable as valid Chrome trace-event JSON from /debug/trace —
    with no OpenTelemetry SDK installed."""
    from pathway_tpu.internals.monitoring_server import start_http_server
    from pathway_tpu.observability import REGISTRY
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

    class DocSchema(pw.Schema):
        data: str

    embedder = SentenceTransformerEmbedder(
        dim=16, depth=1, heads=2, max_len=32, batch_size=8
    )
    docs = pw.debug.table_from_rows(
        DocSchema, [(f"doc {i} topic {i % 3}",) for i in range(4)]
    )
    server = VectorStoreServer(docs, embedder=embedder)
    port = _free_port()
    thread = server.run_server(host="127.0.0.1", port=port, threaded=True)
    try:
        result, headers = None, {}
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                result, headers = _post_retrieve(
                    port, {"query": "topic 1", "k": 2}, FIXED_TRACEPARENT
                )
                if result:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        assert result, "server did not answer a retrieve query"

        # response echoes the trace id with our span id (the header
        # contract: same trace, server-side parent for the caller's logs)
        echoed = tracing.parse_traceparent(headers.get("traceparent"))
        assert echoed is not None and echoed.trace_id == FIXED_TRACE
        assert echoed.span_id != FIXED_SPAN

        def trace_names():
            return {
                r.name
                for r in tracing.get_tracer().spans()
                if r.trace_id == FIXED_TRACE
            }

        # the response leaves from INSIDE the tick; a span is recorded
        # when it closes, so engine.tick lands a moment after the client
        # has its answer — wait for it instead of racing it
        deadline = time.time() + 10
        while "engine.tick" not in trace_names() and time.time() < deadline:
            time.sleep(0.05)
        names = trace_names()
        assert "http.request" in names
        assert "engine.tick" in names
        assert "embed.batch" in names
        assert "knn.search" in names
        assert "vector_store.retrieve" in names
        assert any(n.startswith("op.") for n in names)

        # parent links actually stitch: walking up from knn.search
        # reaches the HTTP root inside one trace
        recs = {
            r.span_id: r
            for r in tracing.get_tracer().spans()
            if r.trace_id == FIXED_TRACE
        }
        knn = next(r for r in recs.values() if r.name == "knn.search")
        hops = []
        cur = knn
        while cur.parent_id is not None and cur.parent_id in recs:
            cur = recs[cur.parent_id]
            hops.append(cur.name)
        assert cur.name == "http.request", hops

        # exemplars: each serving histogram has a child whose exemplar
        # points at this trace. The registry is process-global, so OTHER
        # tests' routes/models own sibling children of the same metric —
        # assert membership, not "the only exemplar".
        exemplars = REGISTRY.exemplars()
        for metric in (
            "pathway_rest_request_seconds",
            "pathway_knn_query_seconds",
        ):
            assert any(
                e["metric"] == metric and e["trace_id"] == FIXED_TRACE
                for e in exemplars
            ), (metric, exemplars)
        # a child keeps its LATEST exemplar, and the answered query's row
        # is retracted a tick later and re-embedded under that tick's own
        # trace: the embed exemplar names our trace or, once that tick
        # has run, the trace of the later embed.batch span
        embed_traces = {
            r.trace_id
            for r in tracing.get_tracer().spans()
            if r.name == "embed.batch"
        }
        assert FIXED_TRACE in embed_traces
        assert any(
            e["metric"] == "pathway_embed_batch_seconds"
            and e["trace_id"] in embed_traces
            for e in exemplars
        ), exemplars

        # /debug/trace round-trips through the schema validator
        mon = start_http_server(None, port=_free_port())
        try:
            url = (
                f"http://127.0.0.1:{mon.server_address[1]}"
                "/debug/trace?seconds=600"
            )
            with urllib.request.urlopen(url, timeout=10) as resp:
                doc = json.loads(resp.read().decode())
            assert tracing.validate_chrome_trace(doc) == []
            traced_names = {
                e["name"]
                for e in doc["traceEvents"]
                if e.get("args", {}).get("trace_id") == FIXED_TRACE
            }
            assert {"http.request", "engine.tick", "knn.search"} <= (
                traced_names
            )
            assert any(
                ex["trace_id"] == FIXED_TRACE
                for ex in doc["otherData"]["exemplars"]
            )
            # bad seconds is a 400, not a 500
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{mon.server_address[1]}"
                    "/debug/trace?seconds=abc",
                    timeout=10,
                )
            assert exc_info.value.code == 400
        finally:
            mon.shutdown()

        # pw.debug notebook surfaces read the same ring
        doc2 = pw.debug.trace(seconds=600)
        assert tracing.validate_chrome_trace(doc2) == []
        tree = pw.debug.trace_tree(FIXED_TRACE)
        assert "http.request" in tree and "knn.search" in tree
    finally:
        try:
            pw.internals.parse_graph.G.runtime.stop()
        except Exception:
            pass
        thread.join(timeout=15)


# --- end-to-end: 2-process host-mesh trace propagation --------------------

DCN_TRACE_SCRIPT = textwrap.dedent(
    """
    import json
    import os

    import pathway_tpu as pw
    from pathway_tpu.observability import tracing

    FIXED = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    pid = int(os.environ["PATHWAY_PROCESS_ID"])
    if pid == 0:
        # simulate a REST request in flight on process 0: its span
        # context must reach process 1 through the mesh frames
        tracing.register_pending(
            7, tracing.parse_traceparent(FIXED)
        )

    class S(pw.Schema):
        word: str

    rows = [(w,) for w in ["a", "b", "a", "c", "b", "a"]]
    t = pw.debug.table_from_rows(S, rows)
    r = t.groupby(t.word).reduce(t.word, count=pw.reducers.count())
    pw.io.null.write(r)
    # go through pw.run (NOT a debug capture): its ambient pathway.run
    # span is exactly what the tick barrier must ignore in favor of the
    # pending request context
    pw.run(monitoring_level="none")

    recs = tracing.get_tracer().spans()
    tick_traces = sorted(
        {r.trace_id for r in recs if r.name == "engine.tick"}
    )
    dcn_names = sorted(
        {r.name for r in recs if r.name.startswith("dcn.")}
    )
    print("TICK_TRACES " + json.dumps(tick_traces), flush=True)
    print("DCN_SPANS " + json.dumps(dcn_names), flush=True)
    """
)


def test_two_process_run_shares_one_trace_id(tmp_path):
    """Acceptance: with two host-mesh processes, spans from both
    processes appear under the same trace id — the traceparent crosses
    the wire inside mesh frames and the lockstep barrier picks one
    group-wide tick trace."""
    from tests.test_distributed import _free_dcn_port, _spawn_group

    script = tmp_path / "dcn_trace.py"
    script.write_text(DCN_TRACE_SCRIPT)
    procs, outs = _spawn_group(script, 2, _free_dcn_port())
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
    per_proc = []
    for out in outs:
        traces = next(
            json.loads(line.split(" ", 1)[1])
            for line in out.splitlines()
            if line.startswith("TICK_TRACES ")
        )
        per_proc.append(set(traces))
    fixed = "ab" * 16
    for i, traces in enumerate(per_proc):
        assert fixed in traces, (
            f"process {i} tick spans missed the propagated trace: "
            f"{per_proc}\n{outs}"
        )
    # the DCN exchange hop is visible on both sides
    for out in outs:
        dcn = next(
            json.loads(line.split(" ", 1)[1])
            for line in out.splitlines()
            if line.startswith("DCN_SPANS ")
        )
        assert "dcn.exchange" in dcn, out
