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


# --- spans inside the two device layers -------------------------------------
# (encoder: embed.batch > embed.tokenize, embed.forward; index:
# index.search > corpus.upload, corpus.prepare, index.topk; all on the
# process's perf_counter clock and on the host plane of a profiler capture)


def _children(records, parent):
    return [r for r in records if r.parent_id == parent.span_id]


def _end(record):
    return record.start_perf_ns + record.duration_ns


def _layer_spans():
    """What the ring holds, without the compile records: an earlier test of
    the process may have installed the jax.monitoring listener."""
    return [r for r in tracing.get_tracer().spans() if r.name != "jax.compile"]


def _one(records, name):
    found = [r for r in records if r.name == name]
    assert len(found) == 1, (name, [r.name for r in records])
    return found[0]


def _toy_embedder():
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    return SentenceTransformerEmbedder(dim=16, depth=1, heads=2, max_len=64)


def _toy_index(rows=5, dim=8, reserved=1024):
    import numpy as np

    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

    index = TpuDenseKnnIndex(dim, "cosine", reserved_space=reserved)
    vectors = np.random.default_rng(7).normal(size=(rows, dim)).astype("float32")
    for key, vector in enumerate(vectors):
        index.upsert(key, vector, None)
    return index, vectors


def test_span_record_is_slotted_and_carries_the_perf_clock():
    tracer = tracing.Tracer(capacity=4)
    before = time.perf_counter_ns()
    with tracer.span("timed"):
        pass
    after = time.perf_counter_ns()
    (rec,) = tracer.spans()
    assert before <= rec.start_perf_ns <= after
    assert rec.start_perf_ns + rec.duration_ns <= after
    # the anchored epoch and the raw read are one read, one anchor apart
    assert rec.start_unix_ns - rec.start_perf_ns == tracing._ANCHOR_NS
    assert rec.to_dict()["start_perf_ns"] == rec.start_perf_ns
    assert not hasattr(rec, "__dict__")  # __slots__: a full ring stays small


def test_default_ring_holds_a_window_and_counts_what_it_drops(monkeypatch):
    monkeypatch.delenv("PATHWAY_TRACE_BUFFER", raising=False)
    assert tracing.Tracer()._spans.maxlen == 65536
    monkeypatch.setenv("PATHWAY_TRACE_BUFFER", "3")
    tracer = tracing.Tracer()
    for i in range(3):
        with tracer.span(f"s{i}"):
            pass
    assert tracer.dropped == 0
    for i in range(3, 8):
        with tracer.span(f"s{i}"):
            pass
    assert tracer.dropped == 5 and [r.name for r in tracer.spans()] == ["s5", "s6", "s7"]
    tracer.clear()
    assert tracer.dropped == 0 and tracer.spans() == []


def test_record_finished_lands_under_the_open_span():
    tracer = tracing.Tracer(capacity=8)
    with tracer.span("step") as step:
        before = time.perf_counter_ns()
        tracer.record_finished("measured.elsewhere", 2_000_000, what="x")
    rec = _one(tracer.spans(), "measured.elsewhere")
    assert rec.parent_id == step.context.span_id and rec.trace_id == step.trace_id
    assert rec.duration_ns == 2_000_000 and rec.attributes == {"what": "x"}
    # it ended when it was recorded
    assert abs(rec.start_perf_ns + rec.duration_ns - before) < 50_000_000
    tracer.record_finished("alone", 1)  # no span open: a root of its own
    assert _one(tracer.spans(), "alone").parent_id is None
    off = tracing.Tracer(capacity=8, enabled=False)
    off.record_finished("never", 1)
    assert off.spans() == []


def test_embed_batch_splits_into_tokenize_and_forward():
    embedder = _toy_embedder()
    texts = ["one two three", "four", "five six seven eight nine ten eleven"]
    embedder._embed_batch(texts)  # compile
    tracing.get_tracer().clear()
    vectors = embedder._embed_batch(texts)
    assert len(vectors) == 3
    records = _layer_spans()
    batch = _one(records, "embed.batch")
    tokenize, forward = _one(records, "embed.tokenize"), _one(records, "embed.forward")
    assert {r.name for r in _children(records, batch)} == {"embed.tokenize", "embed.forward"}
    assert batch.attributes["docs"] == 3 and tokenize.attributes["docs"] == 3
    # one CLS and one id a word: 4 + 2 + 8 real tokens, in a 16-wide length
    # bucket; the 11 words were all in the tokenizer's map from the first call
    assert tokenize.attributes == {"docs": 3, "tokens_real": 14, "len_bucket": 16, "words": 11, "word_hits": 11}
    assert forward.attributes == {
        "groups": 1, "batch_bucket": 8, "len_bucket": 16, "tokens_real": 14, "tokens_padded": 128,
    }
    assert forward.attributes["batch_bucket"] == embedder.runtime.batch_bucket(3)
    assert forward.attributes["tokens_real"] <= forward.attributes["tokens_padded"]
    # the parts lie inside the whole, in order, on one clock
    assert batch.start_perf_ns <= tokenize.start_perf_ns
    assert _end(tokenize) <= forward.start_perf_ns and _end(forward) <= _end(batch)
    assert tokenize.duration_ns + forward.duration_ns <= batch.duration_ns


def test_tokenize_span_counts_the_words_and_the_maps_hits():
    embedder = _toy_embedder()
    texts = ["alpha beta gamma", "delta", "", "epsilon zeta eta theta ? !"]  # 10 distinct words
    seen = []
    for _ in range(2):
        tracing.get_tracer().clear()
        embedder._embed_batch(texts)
        seen.append(_one(_layer_spans(), "embed.tokenize").attributes)
    assert seen[0] == {"docs": 4, "tokens_real": 14, "len_bucket": 16, "words": 10, "word_hits": 0}
    assert seen[1] == {**seen[0], "word_hits": 10}
    for attributes in seen:  # nothing was truncated: every real token but a text's CLS is a word
        assert attributes["words"] == attributes["tokens_real"] - attributes["docs"]
    # half new words: the span carries this call's share, not the tokenizer's totals
    tracing.get_tracer().clear()
    embedder._embed_batch(["alpha beta", "iota kappa"])
    assert _one(_layer_spans(), "embed.tokenize").attributes == {
        "docs": 2, "tokens_real": 6, "len_bucket": 16, "words": 4, "word_hits": 2,
    }
    assert (embedder.tokenizer.words, embedder.tokenizer.word_hits) == (24, 12)


def test_tokenize_span_of_a_tokenizer_without_a_word_map_has_no_word_counts():
    embedder = _toy_embedder()
    plain = embedder.tokenizer

    class NoMap:
        vocab_size = plain.vocab_size

        def encode_batch(self, texts, max_len):
            return plain.encode_batch(texts, max_len)

    embedder.tokenizer = NoMap()
    tracing.get_tracer().clear()
    embedder._embed_batch(["one two three", "four"])
    assert _one(_layer_spans(), "embed.tokenize").attributes == {"docs": 2, "tokens_real": 6, "len_bucket": 16}


@pytest.mark.parametrize(
    "reserved, k, stage1", [(1024, 2, "sort"), (4096, 2, "blockmax"), (4096, 5, "sort")]
)
def test_index_topk_span_says_how_the_topk_starts(reserved, k, stage1):
    from pathway_tpu.ops.knn import topk_stage1

    index, vectors = _toy_index(reserved=reserved)
    tracing.get_tracer().clear()
    index.search([(vectors[0], k, None)])
    topk = _one(_layer_spans(), "index.topk")
    assert topk.attributes == {"kernel": "xla", "stage1": stage1}
    assert stage1 == topk_stage1(index.corpus.capacity, k)


def test_index_search_names_the_refresh_only_when_the_corpus_changed():
    from pathway_tpu.ops.knn import topk_stage1

    index, vectors = _toy_index()
    tracer = tracing.get_tracer()
    first = index.search([(vectors[0], 2, None), (vectors[1], 2, None), (vectors[2], 2, None)])
    records = _layer_spans()
    search = _one(records, "index.search")
    assert [r.name for r in sorted(_children(records, search), key=lambda r: r.start_perf_ns)] == [
        "corpus.upload", "corpus.prepare", "index.topk",
    ]
    assert search.attributes == {"queries": 3, "rows": 5, "bucket": 4, "k": 2}
    upload, prepare = _one(records, "corpus.upload"), _one(records, "corpus.prepare")
    # no device copy yet: the whole mirror, and nothing was noted as changed
    assert upload.attributes == {
        "bytes": 1024 * 8 * 4 + 1024, "rows": 5, "changed_rows": 0, "full": 1,
    }
    assert prepare.attributes == {"metric": "cosine", "bf16": False, "rows": 5}
    # how the top-k starts is read from the program's shapes: the columns
    # it scans (the corpus's capacity, not its 5 rows) and the k it keeps
    assert _one(records, "index.topk").attributes == {
        "kernel": "xla",
        "stage1": topk_stage1(index.corpus.capacity, 2),
    }
    # the four parts add up: self time is what the children leave
    parts = sum(r.duration_ns for r in _children(records, search))
    assert 0 < parts <= search.duration_ns

    tracer.clear()
    assert index.search([(vectors[0], 2, None)] * 3) == [first[0]] * 3
    records = _layer_spans()  # an unchanged corpus: no refresh, no span for one
    assert sorted(r.name for r in records) == ["index.search", "index.topk"]
    assert _one(records, "index.topk").parent_id == _one(records, "index.search").span_id

    tracer.clear()
    index.upsert(99, vectors[0], None)  # a change: the next search pays, and says so
    index.search([(vectors[0], 2, None)])
    records = _layer_spans()
    search = _one(records, "index.search")
    assert [r.name for r in sorted(_children(records, search), key=lambda r: r.start_perf_ns)] == [
        "corpus.upload", "corpus.prepare", "index.topk",
    ]
    # one changed row, handed over in the one chunk every change is cut
    # into (its slots, rows and validity bits) and scattered, not uploaded
    upload, prepare = _one(records, "corpus.upload"), _one(records, "corpus.prepare")
    assert upload.attributes == {
        "bytes": 1024 * (4 + 8 * 4 + 1), "rows": 6, "changed_rows": 1, "full": 0,
    }
    assert prepare.attributes == {"metric": "cosine", "bf16": False, "rows": 6}
    assert _end(upload) <= prepare.start_perf_ns and _end(prepare) <= _end(search)
    tracer.clear()
    empty = type(index)(8, "cosine")
    assert empty.search([(vectors[0], 1, None)]) == [()]  # no corpus: no span
    assert tracer.spans() == []


def test_disabled_tracer_same_answers_no_record_and_no_sync(monkeypatch):
    import jax

    index, vectors = _toy_index()
    embedder = _toy_embedder()
    texts = ["alpha beta", "gamma"]
    tracer = tracing.get_tracer()
    want_hits = index.search([(vectors[3], 3, None)])
    want_vectors = embedder._embed_batch(texts)
    index.upsert(50, vectors[1], None)  # a changed row: the next search refreshes

    syncs = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: syncs.append(1) or x)
    tracer.enabled = False
    tracer.clear()
    hits = index.search([(vectors[3], 3, None)])
    got_vectors = embedder._embed_batch(texts)
    assert tracer.spans() == [] and syncs == []
    assert hits == want_hits
    assert all((a == b).all() for a, b in zip(got_vectors, want_vectors))

    tracer.enabled = True
    index.upsert(51, vectors[2], None)
    index.search([(vectors[3], 3, None)])
    assert len(syncs) == 1  # a scatter refresh waits once, for the arrays it wrote
    index.search([(vectors[3], 3, None)])
    assert len(syncs) == 1  # and an unchanged corpus for nothing
    index.corpus.mirror_replaced()
    index.search([(vectors[3], 3, None)])
    assert len(syncs) == 3  # the whole upload and the whole prepared copy: one each
    tracer.enabled = False
    index.corpus.mirror_replaced()
    index.upsert(52, vectors[2], None)
    index.search([(vectors[3], 3, None)])
    assert len(syncs) == 3  # neither path waits for a disabled tracer


def test_index_search_is_a_child_of_knn_search_in_the_engine():
    import numpy as np

    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnn

    schema = pw.schema_from_types(name=str, vec=np.ndarray)
    rng = np.random.default_rng(0)
    docs = pw.debug.table_from_rows(
        schema, [(f"d{i}", rng.normal(size=4).astype(np.float32)) for i in range(8)]
    )
    queries = pw.debug.table_from_rows(schema, [("q", rng.normal(size=4).astype(np.float32))])
    index = DataIndex(docs, TpuKnn(docs.vec, dimensions=4))
    res = index.query_as_of_now(queries.vec, number_of_matches=2).select(names=pw.right.name)
    _keys, cols = pw.debug.table_to_dicts(res)
    assert len(list(cols["names"].values())[0]) == 2
    records = tracing.get_tracer().spans()
    by_id = {r.span_id: r for r in records}
    searches = [r for r in records if r.name == "index.search"]
    assert searches
    for search in searches:
        assert by_id[search.parent_id].name == "knn.search"
        assert "index.topk" in {r.name for r in _children(records, search)}


def test_a_span_shows_on_the_host_plane_of_a_profiler_capture(tmp_path):
    import glob

    import jax

    index, vectors = _toy_index()
    index.search([(vectors[0], 1, None)])  # compiled before the capture
    index.upsert(7, vectors[1], None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        index.search([(vectors[0], 1, None)])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host_events = {
        e.name
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    assert {"index.search", "corpus.upload", "corpus.prepare", "index.topk"} <= host_events


def test_a_compile_is_recorded_under_the_span_that_forwarded_a_new_shape():
    from pathway_tpu.observability import install_jax_metrics
    from pathway_tpu.observability.registry import MetricsRegistry

    install_jax_metrics(MetricsRegistry())
    embedder = _toy_embedder()
    tracer = tracing.get_tracer()
    embedder._embed_batch(["a b c"])  # the 8 x 16 program
    tracer.clear()
    embedder._embed_batch(["a b c"])
    assert not [r for r in tracer.spans() if r.name == "jax.compile"]
    embedder._embed_batch([" ".join(["w"] * 20)])  # 21 tokens: the 8 x 32 program is new
    records = tracer.spans()
    compiles = [r for r in records if r.name == "jax.compile"]
    assert compiles and all("backend_compile" in r.attributes["event"] for r in compiles)
    forwards = {r.span_id: r for r in records if r.name == "embed.forward"}
    parent = forwards[compiles[0].parent_id]
    assert parent.attributes["len_bucket"] == 32
    assert compiles[0].duration_ns <= parent.duration_ns
    assert "jax.compile" in tracer.format_tree(parent.trace_id)


def test_device_programs_carry_the_scope_names():
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import knn

    q, c = jnp.ones((2, 8)), jnp.ones((1024, 8))
    valid = jnp.ones((1024,), bool)
    prepared = knn.prepare_corpus.lower(c, "cosine", False).as_text(debug_info=True)
    assert "corpus.prepare" in prepared
    topk = knn.dense_topk_prepared.lower(
        q, c, jnp.ones((1024,)), valid, 2, "cosine", False
    ).as_text(debug_info=True)
    assert "knn.scores" in topk and "knn.topk" in topk
    plain = knn.dense_topk.lower(q, c, valid, 2).as_text(debug_info=True)
    assert "knn.scores" in plain and "knn.topk" in plain
    runtime = _toy_embedder().runtime
    ids, mask = jnp.zeros((8, 16), jnp.int32), jnp.ones((8, 16))
    forward = jax.jit(runtime._fwd).lower(runtime.params, ids, mask).as_text(debug_info=True)
    assert "encoder.forward" in forward


def test_ids_are_the_tracers_own_draws():
    import random
    import subprocess
    import sys

    tracer = tracing.Tracer(capacity=8)
    random.seed(1234)  # a caller that seeds the shared generator ...
    with tracer.span("a") as a:
        pass
    random.seed(1234)  # ... twice: the ids do not repeat
    with tracer.span("b") as b:
        pass
    assert a.context.span_id != b.context.span_id and a.trace_id != b.trace_id
    assert tracing.parse_traceparent(a.context.traceparent()) == a.context
    # a forked child draws from a generator seeded anew (a fresh process,
    # so that nothing forks under the test runner's threads)
    script = textwrap.dedent(
        """
        import os
        from pathway_tpu.observability import tracing
        read_end, write_end = os.pipe()
        pid = os.fork()
        if pid == 0:
            os.write(write_end, tracing._new_span_id().encode())
            os._exit(0)
        os.waitpid(pid, 0)
        assert os.read(read_end, 16).decode() != tracing._new_span_id()
        print("distinct")
        """
    )
    done = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=120
    )
    assert done.returncode == 0 and "distinct" in done.stdout, done.stderr
