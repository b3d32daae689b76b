"""Graph Doctor (pathway_tpu.analysis): one positive and one negative
case per rule, the three severity modes of ``pw.run(diagnostics=...)``,
the ``python -m pathway_tpu.analysis`` CLI, and regressions for the
round-5 advice fixes that shipped in the same change."""

import json
import pathlib
import shutil
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import (
    GraphDoctorError,
    Severity,
    rule,
    run_doctor,
    suppress,
)
from pathway_tpu.analysis.rules import RULES

REPO = pathlib.Path(__file__).resolve().parent.parent


# --- fixtures --------------------------------------------------------------


class _ClosedSubject(pw.io.python.ConnectorSubject):
    """Streaming source that produces nothing: enough to mark the input
    unbounded for the static pass without running anything."""

    def run(self) -> None:
        self.close()


class _KV(pw.Schema):
    k: str
    v: int


def _stream():
    return pw.io.python.read(_ClosedSubject(), schema=_KV)


def _static():
    return pw.debug.table_from_markdown(
        """
        k | v
        a | 1
        b | 2
        """
    )


def _static_other():
    # different key set: debug fixtures with identical keys share one
    # Universe, which would defeat the universe-safety cases
    return pw.debug.table_from_markdown(
        """
        k | v
        c | 3
        """
    )


def _rules_of(report):
    return {d.rule for d in report}


# --- rule: dead-node -------------------------------------------------------


def test_dead_node_positive():
    t = _static()
    orphan = t.select(doubled=pw.this.v * 2)  # noqa: F841 — deliberately dead
    pw.io.null.write(t.select(pw.this.k))
    report = run_doctor()
    dead = report.by_rule("dead-node")
    assert len(dead) == 1
    assert dead[0].severity == Severity.WARNING
    assert dead[0].node is orphan._node
    # provenance points at THIS test file
    assert dead[0].node.trace[0].endswith("test_graph_doctor.py")


def test_dead_node_negative():
    t = _static()
    pw.io.null.write(t.select(doubled=pw.this.v * 2))
    assert not run_doctor().by_rule("dead-node")


def test_dead_node_flags_frontier_only():
    # a dead CHAIN yields one diagnostic (the deepest table), not one per node
    t = _static()
    a = t.select(x=pw.this.v + 1)
    b = a.select(y=pw.this.x + 1)  # noqa: F841
    pw.io.null.write(t.select(pw.this.k))
    assert len(run_doctor().by_rule("dead-node")) == 1


# --- rule: dead-column -----------------------------------------------------


def test_dead_column_positive():
    t = _static()
    t2 = t.select(pw.this.k, unused=pw.this.v * 10)
    pw.io.null.write(t2.select(pw.this.k))
    dead = run_doctor().by_rule("dead-column")
    assert [d.data["column"] for d in dead] == ["unused"]
    assert dead[0].severity == Severity.INFO


def test_dead_column_negative_consumed_and_passthrough():
    t = _static()
    # `v` is a zero-cost passthrough reference, `used` is consumed: neither
    # may be flagged
    t2 = t.select(pw.this.k, pw.this.v, used=pw.this.v * 10)
    pw.io.null.write(t2.select(pw.this.k, pw.this.used))
    assert not run_doctor().by_rule("dead-column")


# --- rule: unbounded-state -------------------------------------------------


def test_unbounded_state_streaming_groupby():
    t = _stream()
    r = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.v))
    pw.io.null.write(r)
    found = run_doctor().by_rule("unbounded-state")
    assert len(found) == 1
    assert found[0].severity == Severity.WARNING
    assert "groupby" in found[0].message


def test_unbounded_state_static_groupby_negative():
    t = _static()
    r = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.v))
    pw.io.null.write(r)
    assert not run_doctor().by_rule("unbounded-state")


def test_unbounded_state_streaming_join():
    left, right = _stream(), _stream()
    j = left.join(right, left.k == right.k).select(v1=left.v, v2=right.v)
    pw.io.null.write(j)
    found = run_doctor().by_rule("unbounded-state")
    assert len(found) == 1
    assert "retains every row" in found[0].message


def test_unbounded_state_windowed_with_behavior_negative():
    class _TimedSchema(pw.Schema):
        k: str
        t: int

    t = pw.io.python.read(_ClosedSubject(), schema=_TimedSchema)
    counts = t.windowby(
        pw.this.t,
        window=pw.temporal.tumbling(duration=10),
        instance=pw.this.k,
        behavior=pw.temporal.common_behavior(cutoff=30),
    ).reduce(k=pw.this._pw_instance, n=pw.reducers.count())
    pw.io.null.write(counts)
    # the behavior desugars into a Forget/Freeze guard on the path: no
    # warning-level unbounded-state finding survives
    report = run_doctor()
    assert not [
        d
        for d in report.by_rule("unbounded-state")
        if d.severity >= Severity.WARNING
    ]


# --- rule: universe-safety -------------------------------------------------


def test_universe_safety_unrelated_restrict():
    t1, t2 = _static(), _static_other()
    pw.io.null.write(t2.with_universe_of(t1))
    found = run_doctor().by_rule("universe-safety")
    assert len(found) == 1
    assert found[0].severity == Severity.WARNING


def test_universe_safety_promised_subset_negative():
    t1, t2 = _static(), _static_other()
    t2p = t2.promise_universe_is_subset_of(t1)
    pw.io.null.write(t2p.with_universe_of(t1))
    assert not run_doctor().by_rule("universe-safety")


def test_universe_safety_having_negative():
    # having() IS the sanctioned drop-missing-keys filter; it must not
    # trip the unchecked-restrict warning
    t = _static()
    keys = _static_other().select(ptr=t.pointer_from(pw.this.k))
    pw.io.null.write(t.having(keys.ptr))
    assert not run_doctor().by_rule("universe-safety")


def test_universe_safety_concat_promise_is_info():
    t1, t2 = _static(), _static_other()
    pw.universes.promise_are_pairwise_disjoint(t1, t2)
    pw.io.null.write(t1.concat(t2))
    found = run_doctor().by_rule("universe-safety")
    assert found and all(d.severity == Severity.INFO for d in found)
    assert "PROMISE" in found[0].message


# --- rules: shard safety ---------------------------------------------------


def test_shard_exchange_groupby():
    t = _static()
    r = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.v))
    pw.io.null.write(r)
    found = run_doctor().by_rule("shard-exchange")
    assert len(found) == 1
    # anchored at the GroupByNode (where the exchange happens), which the
    # reduce's rowwise projection consumes
    assert found[0].node is r._node.inputs[0]
    assert type(found[0].node).__name__ == "GroupByNode"
    # routing keys reported in user terms, not prep-column names (_g0)
    assert found[0].data["edges"] == [["k"]]


def test_shard_exchange_map_only_negative():
    t = _static()
    pw.io.null.write(t.select(doubled=pw.this.v * 2))
    assert not run_doctor().by_rule("shard-exchange")


def test_shard_nondeterminism_udf_feeding_groupby():
    @pw.udf(deterministic=False)
    def wobble(x: int) -> int:
        return x

    t = _static()
    t2 = t.select(pw.this.k, w=wobble(pw.this.v))
    r = t2.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.w))
    pw.io.null.write(r)
    found = run_doctor().by_rule("shard-nondeterminism")
    assert len(found) == 1
    assert "wobble" in found[0].message


def test_shard_nondeterminism_deterministic_udf_negative():
    @pw.udf
    def stable(x: int) -> int:
        return x + 1

    t = _static()
    t2 = t.select(pw.this.k, w=stable(pw.this.v))
    r = t2.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.w))
    pw.io.null.write(r)
    assert not run_doctor().by_rule("shard-nondeterminism")


def test_shard_reducer_tuple_vs_sum():
    t = _static()
    r = t.groupby(pw.this.k).reduce(
        pw.this.k,
        hist=pw.reducers.tuple(pw.this.v),
        total=pw.reducers.sum(pw.this.v),
    )
    pw.io.null.write(r)
    found = run_doctor().by_rule("shard-reducer")
    assert len(found) == 1
    assert found[0].data["reducer"] == "tuple"
    # named as the user declared it, not the internal slot (_agg0)
    assert found[0].data["column"] == "hist"


# --- rule: graph-stats -----------------------------------------------------


def test_join_vectorization_env_forced(monkeypatch):
    monkeypatch.setenv("PATHWAY_JOIN_ROWWISE", "1")
    t = _static()
    u = _static()
    j = t.join(u, t.k == u.k).select(t.v)
    pw.io.null.write(j)
    found = run_doctor().by_rule("join-vectorization")
    assert found and found[0].severity == Severity.WARNING
    assert "PATHWAY_JOIN_ROWWISE" in found[0].message


def test_join_vectorization_negative(monkeypatch):
    monkeypatch.delenv("PATHWAY_JOIN_ROWWISE", raising=False)
    t = _static()
    u = _static()
    pw.io.null.write(t.join(u, t.k == u.k).select(t.v))
    assert not run_doctor().by_rule("join-vectorization")


def test_join_vectorization_temporal_joins_info(monkeypatch):
    monkeypatch.delenv("PATHWAY_JOIN_ROWWISE", raising=False)

    class TS(pw.Schema):
        t: int
        v: int

    a = pw.debug.table_from_rows(TS, [(1, 1), (5, 2)])
    b = pw.debug.table_from_rows(TS, [(2, 3), (6, 4)])
    j = a.interval_join_inner(
        b, a.t, b.t, pw.temporal.interval(-2, 2)
    ).select(a.v)
    pw.io.null.write(j)
    found = run_doctor().by_rule("join-vectorization")
    assert found and all(d.severity == Severity.INFO for d in found)
    assert "rowwise" in found[0].message


def test_graph_stats_report():
    t = _static()
    r = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.v))
    pw.io.null.write(r)
    found = run_doctor().by_rule("graph-stats")
    assert len(found) == 1
    msg = found[0].message
    assert "GroupByNode=1" in msg and "stateful" in msg and "exchange" in msg


# --- registry / suppression ------------------------------------------------


def test_custom_rule_registration():
    @rule("test-custom")
    def my_rule(facts):
        from pathway_tpu.analysis import Diagnostic

        yield Diagnostic("test-custom", Severity.INFO, "hello", None)

    try:
        t = _static()
        pw.io.null.write(t.select(pw.this.k))
        assert len(run_doctor().by_rule("test-custom")) == 1
    finally:
        del RULES["test-custom"]


def test_suppress_reaches_operator_under_result_table():
    # unbounded-state anchors at the internal GroupByNode; the user only
    # holds the reduce result — suppressing it must silence the finding
    t = _stream()
    r = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.v))
    pw.io.null.write(r)
    assert run_doctor().by_rule("unbounded-state")
    suppress(r, "unbounded-state")
    assert not run_doctor().by_rule("unbounded-state")
    # other rules anchored at the same operator stay live
    assert run_doctor().by_rule("shard-exchange")


def test_suppress_is_per_node():
    t = _static()
    orphan_a = t.select(x=pw.this.v + 1)
    orphan_b = t.select(y=pw.this.v + 2)  # noqa: F841
    pw.io.null.write(t.select(pw.this.k))
    suppress(orphan_a, "dead-node")
    dead = run_doctor().by_rule("dead-node")
    assert len(dead) == 1
    assert dead[0].node is orphan_b._node


# --- pw.run(diagnostics=...) ----------------------------------------------


def _sick_streaming_pipeline():
    rows = []
    t = _stream()
    r = t.groupby(pw.this.k).reduce(pw.this.k, s=pw.reducers.sum(pw.this.v))
    pw.io.subscribe(r, on_change=lambda **kw: rows.append(kw))
    return rows


def test_run_diagnostics_error_raises_before_execution():
    rows = _sick_streaming_pipeline()
    with pytest.raises(GraphDoctorError) as exc_info:
        pw.run(diagnostics="error")
    assert rows == []  # not a single batch executed
    assert exc_info.value.report.by_rule("unbounded-state")
    assert "unbounded-state" in str(exc_info.value)


def test_run_diagnostics_warn_logs_and_executes(caplog):
    import logging

    rows = _sick_streaming_pipeline()
    with caplog.at_level(logging.WARNING, logger="pathway_tpu.analysis"):
        pw.run(diagnostics="warn")
    assert any("unbounded-state" in r.message for r in caplog.records)


def test_run_diagnostics_off_and_default_execute():
    _sick_streaming_pipeline()
    pw.run(diagnostics="off")
    _sick_streaming_pipeline()
    pw.run()  # default: no doctor pass


def test_run_diagnostics_invalid_value():
    _sick_streaming_pipeline()
    with pytest.raises(ValueError, match="diagnostics"):
        pw.run(diagnostics="loud")


def test_debug_diagnose_scopes_to_table(capsys):
    t = _static()
    unrelated = _static().select(z=pw.this.v * 3)  # noqa: F841
    t2 = t.select(pw.this.k, unused=pw.this.v * 10)
    out = t2.select(pw.this.k)
    report = pw.debug.diagnose(out)
    assert "dead-column" in _rules_of(report)
    # the unrelated pipeline is out of view: no dead-node finding
    assert "dead-node" not in _rules_of(report)
    assert "graph doctor" in capsys.readouterr().out


# --- CLI -------------------------------------------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=240,
    )


def test_cli_demo_reports_five_rule_categories():
    res = _run_cli(
        "--json", "--fail-on", "never", "examples/diagnostics_demo.py"
    )
    assert res.returncode == 0, res.stderr
    findings = json.loads(res.stdout)
    rules_hit = {f["rule"] for f in findings}
    assert len(rules_hit) >= 5, rules_hit
    # every anchored finding carries node provenance
    anchored = [f for f in findings if f["node"] is not None]
    assert anchored
    assert all(
        f["trace"]["file"].endswith("diagnostics_demo.py") for f in anchored
    )


def test_cli_fail_on_threshold():
    assert (
        _run_cli(
            "--fail-on", "warning", "examples/diagnostics_demo.py"
        ).returncode
        == 1
    )
    assert (
        _run_cli("--fail-on", "error", "examples/diagnostics_demo.py").returncode
        == 0
    )


def test_cli_gates_example_pipelines():
    """The CI gate: every in-repo example must be free of error-severity
    findings, and the flagship streaming example free of warnings too.
    The flagship also passes the deployment-plane gate (`--plane
    --json`): plane rules plus the device-free TPU lowering proofs, so
    an unpadded kernel shape fails this suite, not the bench."""
    for script in sorted((REPO / "examples").glob("*.py")):
        res = _run_cli(str(script.relative_to(REPO)))
        assert res.returncode == 0, f"{script.name}:\n{res.stdout}{res.stderr}"
    res = _run_cli("--fail-on", "warning", "examples/streaming_wordcount.py")
    assert res.returncode == 0, res.stdout
    res = _run_cli_plane(
        "--plane",
        "--json",
        "--manifest",
        "none",
        "examples/streaming_wordcount.py",
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert json.loads(res.stdout)["lowering"]["cases"]


def test_cli_rule_filter():
    res = _run_cli(
        "--json",
        "--fail-on",
        "never",
        "--rule",
        "graph-stats",
        "examples/streaming_wordcount.py",
    )
    assert res.returncode == 0, res.stderr
    findings = json.loads(res.stdout)
    assert {f["rule"] for f in findings} == {"graph-stats"}


def test_cli_unknown_rule_id_is_usage_error():
    res = _run_cli(
        "--rule", "bogus-rule", "examples/streaming_wordcount.py"
    )
    assert res.returncode == 2
    assert "unknown rule id" in res.stderr


@pytest.mark.skipif(shutil.which("ruff") is None, reason="ruff not installed")
def test_analysis_package_is_lint_clean():
    res = subprocess.run(
        [
            "ruff",
            "check",
            "pathway_tpu/analysis",
            "tests/test_graph_doctor.py",
        ],
        capture_output=True,
        text=True,
        cwd=REPO,
        timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr


# --- regressions for the round-5 advice fixes ------------------------------


class _NdArraySchema(pw.Schema):
    key: np.ndarray
    v: int


def test_join_on_object_column_with_ndarray_values():
    """nodes.py null-join-key mask: object-dtype on-columns holding
    ndarrays used to raise 'truth value of an array is ambiguous'."""
    t1 = pw.debug.table_from_rows(
        _NdArraySchema,
        [(np.array([1, 2]), 10), (np.array([3, 4]), 20)],
    )
    t2 = pw.debug.table_from_rows(
        _NdArraySchema,
        [(np.array([1, 2]), 100), (np.array([9, 9]), 200)],
    )
    j = t1.join(t2, t1.key == t2.key).select(v1=t1.v, v2=t2.v)
    keys, cols = pw.debug.table_to_dicts(j)
    assert [(cols["v1"][k], cols["v2"][k]) for k in keys] == [(10, 100)]


def test_host_mesh_secret_mismatch_fails_fast(monkeypatch):
    """host_exchange handshake: a PATHWAY_DCN_SECRET mismatch must fail
    at dial time with an authentication error, not a later EPIPE."""
    from pathway_tpu.parallel import host_exchange as hx

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    base = sock.getsockname()[1]
    sock.close()

    monkeypatch.setenv("PATHWAY_DCN_SECRET", "secret-A")
    mesh0_box = {}

    def build_mesh0():
        try:
            mesh0_box["mesh"] = hx.HostMesh(2, 0, base, connect_timeout=30.0)
        except hx.HostMeshError as e:  # peer 1 dials us with the wrong key
            mesh0_box["err"] = e

    t0 = threading.Thread(target=build_mesh0, daemon=True)
    t0.start()
    time.sleep(0.3)  # mesh0's listener is up; now dial with the wrong key
    monkeypatch.setenv("PATHWAY_DCN_SECRET", "secret-B")
    with pytest.raises(hx.HostMeshError, match="authentication failed"):
        hx.HostMesh(2, 1, base, connect_timeout=8.0)
    t0.join(30)
    mesh = mesh0_box.get("mesh")
    if mesh is not None:
        mesh.close()


def test_host_mesh_matching_secret_still_connects(monkeypatch):
    from pathway_tpu.parallel import host_exchange as hx

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    base = sock.getsockname()[1]
    sock.close()

    monkeypatch.setenv("PATHWAY_DCN_SECRET", "shared-secret")
    meshes = [None, None]

    def build(pid):
        meshes[pid] = hx.HostMesh(2, pid, base, connect_timeout=30.0)

    threads = [
        threading.Thread(target=build, args=(pid,), daemon=True)
        for pid in (0, 1)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    m0, m1 = meshes
    assert m0 is not None and m1 is not None
    try:
        m0.send(1, "ch", 0, {"ok": True})
        assert m1.gather("ch", 0, timeout=30) == {0: {"ok": True}}
    finally:
        m0.close()
        m1.close()


def test_asof_now_duplicate_id_poisons_row_not_run():
    """AsofNowJoin id=pw.left.id duplicate matches: recorded via
    record_error so terminate_on_error=False runs keep going, while the
    default run surfaces the ValueError."""
    from pathway_tpu.internals.errors import peek_errors

    def declare():
        queries = pw.debug.table_from_markdown(
            """
            q | __time__
            1 | 4
            2 | 4
            """
        )
        state = pw.debug.table_from_markdown(
            """
            q  | v  | __time__
            1  | 10 | 2
            1  | 11 | 2
            2  | 20 | 2
            """
        )
        res = queries.asof_now_join(
            state, queries.q == state.q, id=queries.id
        ).select(q=queries.q, v=state.v)
        rows = []
        pw.io.subscribe(
            res, on_change=lambda key, row, time, is_addition: rows.append(row)
        )
        return rows

    rows = declare()
    pw.run(terminate_on_error=False)
    # q=1 matched two rows -> poisoned/skipped; q=2 still flows
    assert rows == [{"q": 2, "v": 20}]
    errs = peek_errors()
    assert any("id contract" in e["message"] for e in errs)

    from pathway_tpu.internals import parse_graph
    from pathway_tpu.internals.errors import clear_errors

    parse_graph.G.clear()
    clear_errors()
    declare()
    with pytest.raises(ValueError, match="id contract"):
        pw.run()  # terminate_on_error=True default


# --- rule: unreplicated-serving (Replica Shield) ---------------------------


def _gated_index_graph(tmp_port=18099):
    """Gated REST ingress + an external index: the serving topology the
    unreplicated-serving rule inspects."""
    from pathway_tpu.io.http import rest_connector
    from pathway_tpu.serving import QoSConfig
    from pathway_tpu.stdlib.indexing import DataIndex, TpuKnn

    docs = pw.debug.table_from_rows(
        pw.schema_from_types(vec=np.ndarray),
        [(np.asarray([1.0, 0.0], dtype=np.float32),)],
    )
    queries, _writer = rest_connector(
        host="127.0.0.1",
        port=tmp_port,
        schema=pw.schema_from_types(q=str),
        route="/knn",
        qos=QoSConfig(),
    )
    qvec = queries.select(
        vec=pw.apply(
            lambda s: np.asarray([1.0, 0.0], dtype=np.float32), queries.q
        )
    )
    index = DataIndex(docs, TpuKnn(docs.vec, dimensions=2))
    reply = index.query_as_of_now(qvec.vec, number_of_matches=1)
    pw.io.null.write(reply.select(score=pw.right._pw_index_reply_score))
    return queries


def test_unreplicated_serving_warns_without_responder_or_replicas(
    monkeypatch,
):
    from pathway_tpu.serving import degrade

    monkeypatch.delenv("PATHWAY_SERVING_REPLICAS", raising=False)
    degrade.reset()
    _gated_index_graph()
    found = run_doctor().by_rule("unreplicated-serving")
    assert len(found) == 1
    assert found[0].severity == Severity.WARNING
    assert "hard-503" in found[0].message


def test_unreplicated_serving_negative_with_stale_responder(monkeypatch):
    from pathway_tpu.serving import degrade

    monkeypatch.delenv("PATHWAY_SERVING_REPLICAS", raising=False)
    degrade.reset()
    _gated_index_graph(tmp_port=18100)
    degrade.register_stale_responder("/knn", lambda vals: {"stale": True})
    try:
        assert not run_doctor().by_rule("unreplicated-serving")
    finally:
        degrade.reset()


def test_unreplicated_serving_info_when_staleness_unbounded(monkeypatch):
    from pathway_tpu.serving import degrade

    degrade.reset()
    monkeypatch.setenv(
        "PATHWAY_SERVING_REPLICAS",
        "http://127.0.0.1:9101,http://127.0.0.1:9102",
    )
    monkeypatch.delenv("PATHWAY_SERVING_MAX_STALENESS_MS", raising=False)
    # a standby writer is configured: the ingest-SPOF facet stays quiet
    monkeypatch.setenv("PATHWAY_REPL_STANDBY", "127.0.0.1:9200")
    _gated_index_graph(tmp_port=18101)
    found = run_doctor().by_rule("unreplicated-serving")
    assert len(found) == 1
    assert found[0].severity == Severity.INFO
    assert "max-staleness" in found[0].message
    # bounding staleness clears the finding
    monkeypatch.setenv("PATHWAY_SERVING_MAX_STALENESS_MS", "2000")
    assert not run_doctor().by_rule("unreplicated-serving")


def test_unreplicated_serving_warns_missing_standby_writer(monkeypatch):
    """Shard Harbor facet: a replicated read plane whose single ingest
    writer has no standby is still an SPOF — kill the writer and every
    replica serves permanently stale data."""
    from pathway_tpu.serving import degrade

    degrade.reset()
    monkeypatch.setenv(
        "PATHWAY_SERVING_REPLICAS",
        "http://127.0.0.1:9101,http://127.0.0.1:9102",
    )
    monkeypatch.setenv("PATHWAY_SERVING_MAX_STALENESS_MS", "2000")
    monkeypatch.delenv("PATHWAY_REPL_STANDBY", raising=False)
    _gated_index_graph(tmp_port=18103)
    found = run_doctor().by_rule("unreplicated-serving")
    assert len(found) == 1
    assert found[0].severity == Severity.WARNING
    assert "standby" in found[0].message
    # configuring the standby clears it
    monkeypatch.setenv("PATHWAY_REPL_STANDBY", "127.0.0.1:9200")
    assert not run_doctor().by_rule("unreplicated-serving")


def test_unreplicated_serving_info_single_owner_shard(monkeypatch):
    """Shard Harbor facet: a shard with one owner turns any member
    death into a partial-corpus outage (503 naming the shard)."""
    from pathway_tpu.serving import degrade

    degrade.reset()
    monkeypatch.setenv(
        "PATHWAY_SERVING_REPLICAS",
        "http://127.0.0.1:9101,http://127.0.0.1:9102",
    )
    monkeypatch.setenv("PATHWAY_SERVING_MAX_STALENESS_MS", "2000")
    monkeypatch.setenv("PATHWAY_REPL_STANDBY", "127.0.0.1:9200")
    monkeypatch.setenv(
        "PATHWAY_SERVING_SHARD_MAP",
        "http://127.0.0.1:9101,http://127.0.0.1:9102|http://127.0.0.1:9103",
    )
    _gated_index_graph(tmp_port=18104)
    found = run_doctor().by_rule("unreplicated-serving")
    assert len(found) == 1
    assert found[0].severity == Severity.INFO
    assert "single owner" in found[0].message
    assert found[0].data["single_owner_shards"] == [1]
    # two members per shard clears it
    monkeypatch.setenv(
        "PATHWAY_SERVING_SHARD_MAP",
        "http://127.0.0.1:9101,http://127.0.0.1:9102"
        "|http://127.0.0.1:9103,http://127.0.0.1:9104",
    )
    assert not run_doctor().by_rule("unreplicated-serving")
    # shard-count form (no map): 2 replicas over 3 shards pigeonholes
    # at least one single-owner shard — the finding names the counts,
    # not invented shard ids
    monkeypatch.delenv("PATHWAY_SERVING_SHARD_MAP", raising=False)
    monkeypatch.setenv("PATHWAY_SERVING_SHARDS", "3")
    found = run_doctor().by_rule("unreplicated-serving")
    assert [f.severity for f in found] == [Severity.INFO]
    assert "at least one shard" in found[0].message
    assert found[0].data == {"shards": 3, "replicas": 2}
    # 6 replicas over 3 shards CAN give every shard two owners: quiet
    monkeypatch.setenv(
        "PATHWAY_SERVING_REPLICAS",
        ",".join(f"http://127.0.0.1:91{i:02d}" for i in range(6)),
    )
    assert not run_doctor().by_rule("unreplicated-serving")


def test_unreplicated_serving_negative_without_index(monkeypatch):
    """A gated REST endpoint with no external index in the graph is not
    a serving plane — the rule stays quiet."""
    from pathway_tpu.io.http import rest_connector
    from pathway_tpu.serving import QoSConfig, degrade

    monkeypatch.delenv("PATHWAY_SERVING_REPLICAS", raising=False)
    degrade.reset()
    queries, writer = rest_connector(
        host="127.0.0.1",
        port=18102,
        schema=pw.schema_from_types(q=str),
        route="/echo",
        qos=QoSConfig(),
    )
    writer(queries.select(query_id=queries.id, result=queries.q))
    assert not run_doctor().by_rule("unreplicated-serving")


# --- plane doctor: deployment-scope rules (analysis/plane.py) --------------


import os  # noqa: E402

from pathway_tpu.analysis import run_plane_doctor  # noqa: E402


@pytest.fixture
def _clean_knobs(monkeypatch):
    """Strip ambient PATHWAY_* knobs so env-lint assertions are exact."""
    for k in list(os.environ):
        if k.startswith("PATHWAY_"):
            monkeypatch.delenv(k, raising=False)


def _monolith_graph():
    """One graph touching all four arranged-state gaps (ROADMAP 5c):
    UpdateRows, instance-less Sort, Ix, UniverseSetOp."""
    t = _static()
    u = _static_other()
    t.update_rows(u)
    t.sort(key=pw.this.v)
    keys = u.select(ptr=t.pointer_from(pw.this.k))
    t.ix(keys.ptr)
    u.with_universe_of(t)
    return t


def test_snapshot_coverage_names_the_four_monoliths(_clean_knobs):
    _monolith_graph()
    found = run_plane_doctor().by_rule("snapshot-coverage")
    execs = {d.data["exec"] for d in found}
    assert execs >= {
        "UpdateRowsExec",
        "SortExec",
        "IxExec",
        "UniverseSetOpExec",
    }, execs
    assert all(d.severity == Severity.WARNING for d in found)


def test_snapshot_coverage_skips_arrangement_backed_execs(_clean_knobs):
    t = _stream()
    t.groupby(pw.this.k).reduce(
        pw.this.k, total=pw.reducers.sum(pw.this.v)
    )
    execs = {
        d.data["exec"]
        for d in run_plane_doctor().by_rule("snapshot-coverage")
    }
    assert "GroupByExec" not in execs


def test_snapshot_coverage_clears_when_arranged_state_lands(
    _clean_knobs, monkeypatch
):
    """The audit is driven by the exec metadata, not a hardcoded list:
    giving UpdateRowsExec an arranged_state override clears it."""
    from pathway_tpu.engine import nodes as en

    t = _static()
    t.update_rows(_static_other())
    before = {
        d.data["exec"]
        for d in run_plane_doctor().by_rule("snapshot-coverage")
    }
    assert "UpdateRowsExec" in before

    monkeypatch.setattr(
        en.UpdateRowsExec,
        "arranged_state",
        lambda self: {},
        raising=False,
    )
    after = {
        d.data["exec"]
        for d in run_plane_doctor().by_rule("snapshot-coverage")
    }
    assert "UpdateRowsExec" not in after


def test_snapshot_coverage_per_node_suppression(_clean_knobs):
    t = _static()
    upd = t.update_rows(_static_other())
    suppress(upd, "snapshot-coverage")
    execs = {
        d.data["exec"]
        for d in run_plane_doctor().by_rule("snapshot-coverage")
    }
    assert "UpdateRowsExec" not in execs


def test_pickle_hot_path_flags_object_exchange_key(_clean_knobs):
    t = _static()  # k: str
    t.groupby(pw.this.k).reduce(
        pw.this.k, total=pw.reducers.sum(pw.this.v)
    )
    found = run_plane_doctor().by_rule("pickle-hot-path")
    assert found, "str groupby key should be flagged on the wire"
    assert any("str" in d.data["dtype"] for d in found)


def test_pickle_hot_path_quiet_on_numeric_columns(_clean_knobs):
    t = _static()
    t.groupby(pw.this.v).reduce(
        pw.this.v, n=pw.reducers.count()
    )
    numeric_only = t.select(v=pw.this.v)
    numeric_only.groupby(pw.this.v).reduce(
        pw.this.v, n=pw.reducers.count()
    )
    found = run_plane_doctor().by_rule("pickle-hot-path")
    # the int key column itself must not be flagged
    assert all("int" not in d.data["dtype"] for d in found)


def test_knob_lint_shard_count_disagreement(_clean_knobs, monkeypatch):
    """The satellite case: PATHWAY_SERVING_SHARDS says 3 but the shard
    map describes 2 — an ERROR before any process boots."""
    monkeypatch.setenv("PATHWAY_SERVING_SHARDS", "3")
    monkeypatch.setenv(
        "PATHWAY_SERVING_SHARD_MAP", "h1:9000|h2:9001"
    )
    found = run_plane_doctor().by_rule("knob-coherence")
    conflict = [d for d in found if "conflicting shard counts" in d.message]
    assert conflict and conflict[0].severity == Severity.ERROR
    assert conflict[0].data["shards"] == 3
    assert conflict[0].data["map_shards"] == 2

    # agreement clears it
    monkeypatch.setenv("PATHWAY_SERVING_SHARDS", "2")
    found = run_plane_doctor().by_rule("knob-coherence")
    assert not [d for d in found if "conflicting" in d.message]


def test_knob_lint_torn_shard_map_and_bad_qos(_clean_knobs, monkeypatch):
    monkeypatch.setenv("PATHWAY_SERVING_SHARD_MAP", "|||")
    monkeypatch.setenv("PATHWAY_SERVING_MAX_QUEUE", "many")
    found = run_plane_doctor().by_rule("knob-coherence")
    msgs = [d.message for d in found if d.severity == Severity.ERROR]
    assert any("SHARD_MAP" in m for m in msgs)
    assert any("MAX_QUEUE" in m for m in msgs)


def test_knob_lint_gated_ingress_without_deadline(
    _clean_knobs, monkeypatch
):
    monkeypatch.setenv("PATHWAY_SERVING_ENABLED", "1")
    monkeypatch.setenv("PATHWAY_SERVING_DEADLINE_MS", "0")
    found = run_plane_doctor().by_rule("knob-coherence")
    assert any(
        "without deadline bounds" in d.message
        and d.severity == Severity.WARNING
        for d in found
    )


def test_knob_lint_cache_without_stream_and_inert_tenancy(
    _clean_knobs, monkeypatch
):
    monkeypatch.setenv("PATHWAY_ROUTER_CACHE", "1")
    monkeypatch.setenv("PATHWAY_TENANT_QOS", "1")
    found = run_plane_doctor().by_rule("knob-coherence")
    assert any(
        "PATHWAY_ROUTER_CACHE_WRITER" in d.message
        and d.severity == Severity.WARNING
        for d in found
    )
    assert any(
        "PATHWAY_TENANT_QOS" in d.message
        and d.severity == Severity.INFO
        for d in found
    )


def test_knob_lint_quiet_on_clean_env(_clean_knobs):
    assert not run_plane_doctor().by_rule("knob-coherence")


# --- plane mode CLI (the tier-1 lowering gate) -----------------------------


def _run_cli_plane(*args, env_overrides=None):
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith("PATHWAY_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_overrides or {})
    return subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
        timeout=240,
    )


def test_cli_plane_proves_all_families_and_writes_manifest(tmp_path):
    """The tier-1 gate half 1: `--plane` lowers every kernel family
    across the pad ladder with zero device access (JAX_PLATFORMS=cpu)
    and writes the content-addressed manifest."""
    manifest = tmp_path / "LOWERING_r16.json"
    res = _run_cli_plane(
        "--plane", "--json", "--manifest", str(manifest)
    )
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["findings"] == []
    cases = doc["lowering"]["cases"]
    families = {c["family"] for c in cases}
    assert families >= {"paged_attention", "tick_forge"}
    assert all(
        c["status"] in ("lowered", "rejected") for c in cases
    ), cases
    # every expected-lower case really went through Mosaic lowering
    for c in cases:
        if c["status"] == "lowered":
            assert len(c["stablehlo_sha256"]) == 64
    ondisk = json.loads(manifest.read_text())
    assert ondisk["content_sha256"] == doc["lowering"]["content_sha256"]


def test_cli_plane_fails_suite_on_unpadded_shape(tmp_path):
    """The tier-1 gate half 2: a newly introduced unpadded kernel shape
    fails the suite (exit 1) with a finding naming the kernel, the
    shape and the violated rule — not the bench."""
    res = _run_cli_plane(
        "--plane",
        "--json",
        "--manifest",
        "none",
        "--prove-shape",
        "paged_attention:head_dim=129",
    )
    assert res.returncode == 1, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    (finding,) = [
        f for f in doc["findings"] if f["rule"] == "tpu-lowering"
    ]
    assert finding["severity"] == "error"
    assert finding["data"]["family"] == "paged_attention"
    assert finding["data"]["shape"]["head_dim"] == 129
    assert finding["data"]["rule"] == "lane-pad"


def test_cli_plane_env_findings_and_knob_snapshot(tmp_path):
    res = _run_cli_plane(
        "--plane",
        "--json",
        "--manifest",
        "none",
        env_overrides={
            "PATHWAY_SERVING_SHARDS": "3",
            "PATHWAY_SERVING_SHARD_MAP": "h1:9000|h2:9001",
        },
    )
    assert res.returncode == 1, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert any(
        f["rule"] == "knob-coherence" and f["severity"] == "error"
        for f in doc["findings"]
    )
    # the knob snapshot records the deployment the verdict applied to
    assert doc["knobs"]["PATHWAY_SERVING_SHARDS"] == "3"


def test_cli_plane_with_script_runs_both_scopes():
    res = _run_cli_plane(
        "--plane",
        "--json",
        "--manifest",
        "none",
        "--fail-on",
        "never",
        "examples/diagnostics_demo.py",
    )
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    rules_hit = {f["rule"] for f in doc["findings"]}
    # graph rules and the lowering proofs land in ONE report
    assert "dead-node" in rules_hit or "dead-column" in rules_hit
    assert doc["lowering"] is not None
    assert {c["family"] for c in doc["lowering"]["cases"]} >= {
        "paged_attention",
        "tick_forge",
    }


def test_cli_requires_script_unless_plane():
    res = _run_cli()
    assert res.returncode == 2
    assert "script is required" in res.stderr
