"""Multi-process execution smoke tests: two real OS
processes join via jax.distributed (gloo CPU collectives) and run the
corpus-sharded KNN with a true cross-process collective merge, asserting
exact equality with a single-process reference. Pattern: reference
integration_tests/wordcount spawns real process groups."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_WORKER = textwrap.dedent(
    """
    import os, sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from pathway_tpu.parallel import distributed as dist

    assert dist.maybe_initialize(), "expected multi-process mode"
    assert jax.process_count() == 2, jax.process_count()

    pid = jax.process_index()
    n_global, dim, k = 64, 16, 5
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(n_global, dim)).astype(np.float32)
    valid = np.ones(n_global, bool)
    valid[7] = False
    queries = rng.normal(size=(3, dim)).astype(np.float32)

    half = n_global // 2
    lo, hi = pid * half, (pid + 1) * half
    sc, ix = dist.sharded_topk_global(
        queries, corpus[lo:hi], valid[lo:hi], k, metric="cosine"
    )

    # single-device reference on the full corpus
    from pathway_tpu.ops.knn import dense_topk
    import jax.numpy as jnp
    s_ref, i_ref = dense_topk(
        jnp.asarray(queries), jnp.asarray(corpus), jnp.asarray(valid),
        k, metric="cosine",
    )
    assert (np.asarray(i_ref) == ix).all(), (np.asarray(i_ref), ix)
    assert np.allclose(np.asarray(s_ref), sc, atol=1e-5)
    print(f"WORKER-OK pid={pid}", flush=True)
    """
)


def test_two_process_sharded_knn(tmp_path):
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            PATHWAY_PROCESSES="2",
            PATHWAY_PROCESS_ID=str(pid),
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            PYTHONPATH=os.path.dirname(os.path.dirname(__file__)),
        )
        env.pop("XLA_FLAGS", None)  # one CPU device per process
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    try:
        outs = [p.communicate(timeout=150)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"pid={pid} failed:\n{out[-3000:]}"
        assert f"WORKER-OK pid={pid}" in out


def test_process_env_defaults(monkeypatch):
    from pathway_tpu.parallel import distributed as dist

    monkeypatch.delenv("PATHWAY_PROCESSES", raising=False)
    monkeypatch.delenv("PATHWAY_PROCESS_ID", raising=False)
    monkeypatch.delenv("PATHWAY_FIRST_PORT", raising=False)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    n, pid, coord = dist.process_env()
    assert (n, pid) == (1, 0) and coord.startswith("127.0.0.1:")
    assert dist.maybe_initialize() is False  # single process: no-op

    monkeypatch.setenv("PATHWAY_PROCESSES", "4")
    monkeypatch.setenv("PATHWAY_PROCESS_ID", "3")
    monkeypatch.setenv("PATHWAY_FIRST_PORT", "12345")
    n, pid, coord = dist.process_env()
    assert (n, pid, coord) == (4, 3, "127.0.0.1:12345")


# ---------------------------------------------------------------------------
# DCN rung: cross-process host-row exchange

_DCN_WORDCOUNT = textwrap.dedent(
    """
    import os, json
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    pid = int(os.environ["PATHWAY_PROCESS_ID"])

    class S(pw.Schema):
        word: str

    words_all = [f"w{i % 7}" for i in range(100)]
    mine = [(w,) for i, w in enumerate(words_all) if i % 2 == pid]
    t = pw.debug.table_from_rows(S, mine)
    r = t.groupby(t.word).reduce(t.word, count=pw.reducers.count())
    keys, cols = pw.debug.table_to_dicts(r)
    out = {cols["word"][k]: cols["count"][k] for k in keys}
    rt = pw.internals.parse_graph.G.last_runtime
    from pathway_tpu.engine.dcn import DcnGroupByExec
    gbs = [e for e in rt.execs.values() if isinstance(e, DcnGroupByExec)]
    assert gbs, "expected a DCN groupby exec"
    assert gbs[0].router.exchanges > 0, "no cross-process exchange ran"
    owned = sorted(gbs[0].owned_group_keys())
    print("RESULT " + json.dumps(out), flush=True)
    print("OWNED " + json.dumps(owned), flush=True)
    """
)


def _spawn_group(script_path, n, port, extra_env=None, timeout=150):
    procs = []
    job_secret = "test-job-secret-%d" % port
    for pid in range(n):
        env = dict(os.environ)
        env.update(
            PATHWAY_PROCESSES=str(n),
            PATHWAY_PROCESS_ID=str(pid),
            PATHWAY_DCN_PORT=str(port),
            PATHWAY_DCN_SECRET=job_secret,
            JAX_PLATFORMS="cpu",
            PYTHONPATH=os.path.dirname(os.path.dirname(__file__)),
        )
        env.pop("XLA_FLAGS", None)
        if extra_env:
            env.update(extra_env(pid) or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script_path)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    try:
        # a child's log lines (stderr) after the lines the tests parse
        # (stdout), never between them: on one pipe a warning could land
        # inside a "RESULT {...}" line
        outs = ["\n".join(p.communicate(timeout=timeout)) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def _free_dcn_port() -> int:
    from pathway_tpu.testing.chaos import free_dcn_port

    return free_dcn_port(2)


def test_two_process_wordcount_dcn(tmp_path):
    """Host rows cross processes: 2-process wordcount where each process
    owns disjoint group-key shards and merged totals equal the
    single-process result (reference: timely TCP mesh Exchange,
    external/timely-dataflow/communication/src/networking.rs:16-33)."""
    script = tmp_path / "worker.py"
    script.write_text(_DCN_WORDCOUNT)
    procs, outs = _spawn_group(script, 2, _free_dcn_port())
    results, owned = [], []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"pid={pid} failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results.append(json.loads(line[len("RESULT "):]))
            elif line.startswith("OWNED "):
                owned.append(set(json.loads(line[len("OWNED "):])))
    assert len(results) == 2 and len(owned) == 2
    # disjoint ownership, both processes hold real state
    assert owned[0] and owned[1] and not (owned[0] & owned[1])
    # no word is reported by both processes
    assert not (set(results[0]) & set(results[1]))
    merged: dict[str, int] = {}
    for r in results:
        merged.update(r)
    expected = {f"w{j}": len([i for i in range(100) if i % 7 == j]) for j in range(7)}
    assert merged == expected


_DCN_KILL_WORKER = textwrap.dedent(
    """
    import os, json, threading, time, pathlib
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    pid = int(os.environ["PATHWAY_PROCESS_ID"])
    base = pathlib.Path(os.environ["PW_TEST_DIR"])
    in_dir = base / f"in{pid}"
    pdir = base / f"pstorage{pid}"
    out_file = base / f"out{pid}_{os.environ['PW_PHASE']}.jsonl"
    stop_file = base / "STOP"
    die_after = int(os.environ.get("PW_DIE_AFTER_ROWS", "0"))

    class S(pw.Schema):
        word: str

    t = pw.io.jsonlines.read(str(in_dir), schema=S, mode="streaming")
    r = t.groupby(t.word).reduce(t.word, count=pw.reducers.count())
    pw.io.jsonlines.write(r, str(out_file))

    def watch():
        while True:
            time.sleep(0.05)
            try:
                n = sum(1 for _ in open(out_file))
            except OSError:
                n = 0
            if die_after and n >= die_after:
                os._exit(17)
            if stop_file.exists():
                rt = pw.internals.parse_graph.G.runtime
                if rt is not None:
                    rt.stop()
                return

    threading.Thread(target=watch, daemon=True).start()
    cfg = pw.persistence.Config.simple_config(
        pw.persistence.Backend.filesystem(str(pdir)),
    )
    pw.run(persistence_config=cfg, autocommit_duration_ms=20)
    print("CLEAN-EXIT", flush=True)
    """
)


def _fold_updates(paths) -> dict:
    state: dict = {}
    for p in paths:
        try:
            lines = open(p).read().splitlines()
        except OSError:
            continue
        for line in lines:
            if not line.strip():
                continue
            o = json.loads(line)
            if o["diff"] > 0:
                state[o["word"]] = o["count"]
            elif state.get(o["word"]) == o["count"]:
                del state[o["word"]]
    return state


def test_two_process_wordcount_kill_restart(tmp_path):
    """One process is killed mid-stream; the group fail-stops; a full
    restart resumes from persisted state (per-process input logs +
    group-safe operator snapshots) and the merged totals exactly match —
    no row lost, none double-counted (reference recovery model:
    whole-cluster restart from the persisted frontier,
    src/persistence/state.rs:291)."""
    base = tmp_path / "work"
    for pid in range(2):
        (base / f"in{pid}").mkdir(parents=True)
    script = tmp_path / "worker.py"
    script.write_text(_DCN_KILL_WORKER)
    port = _free_dcn_port()

    def write_words(pid, fname, words):
        with open(base / f"in{pid}" / fname, "w") as f:
            for w in words:
                f.write(json.dumps({"word": w}) + "\n")

    write_words(0, "f1.jsonl", ["a", "b", "a", "c", "a", "d", "b"])
    write_words(1, "f1.jsonl", ["b", "c", "e", "a", "e", "f", "a"])

    # phase 1: process 1 kills itself after 3 output rows; process 0
    # fail-stops at the next barrier (HostMeshError)
    procs, outs = _spawn_group(
        script,
        2,
        port,
        extra_env=lambda pid: {
            "PW_TEST_DIR": str(base),
            "PW_PHASE": "1",
            **({"PW_DIE_AFTER_ROWS": "3"} if pid == 1 else {}),
        },
        timeout=90,
    )
    assert procs[1].returncode == 17, outs[1][-2000:]
    assert procs[0].returncode != 0, outs[0][-2000:]
    assert "HostMeshError" in outs[0]

    # phase 2: more input, full-group restart from persistence
    write_words(0, "f2.jsonl", ["a", "g", "d"])
    write_words(1, "f2.jsonl", ["g", "b", "e"])
    expected = {"a": 6, "b": 4, "c": 2, "d": 2, "e": 3, "f": 1, "g": 2}

    import threading

    def stopper():
        deadline = time.time() + 70
        while time.time() < deadline:
            merged = {}
            for pid in range(2):
                merged.update(
                    _fold_updates(
                        [
                            base / f"out{pid}_1.jsonl",
                            base / f"out{pid}_2.jsonl",
                        ]
                    )
                )
            if merged == expected:
                break
            time.sleep(0.2)
        (base / "STOP").touch()

    stop_thread = threading.Thread(target=stopper, daemon=True)
    stop_thread.start()
    procs2, outs2 = _spawn_group(
        script,
        2,
        port,
        extra_env=lambda pid: {"PW_TEST_DIR": str(base), "PW_PHASE": "2"},
        timeout=120,
    )
    stop_thread.join(timeout=90)
    for pid, (p, out) in enumerate(zip(procs2, outs2)):
        assert p.returncode == 0, f"phase2 pid={pid}:\n{out[-3000:]}"
        assert "CLEAN-EXIT" in out
    merged = {}
    for pid in range(2):
        merged.update(
            _fold_updates(
                [base / f"out{pid}_1.jsonl", base / f"out{pid}_2.jsonl"]
            )
        )
    assert merged == expected


_DCN_MATRIX_WORKER = textwrap.dedent(
    """
    import os, sys, json, time, pathlib, threading
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    pid = int(os.environ["PATHWAY_PROCESS_ID"])
    base = pathlib.Path(os.environ["PW_TEST_DIR"])
    in_dir = base / f"in{pid}"
    pdir = base / f"pstorage{pid}"
    out_file = base / f"out{pid}_{os.environ['PW_PHASE']}.jsonl"
    stop_file = base / "STOP"
    die_after = int(os.environ.get("PW_DIE_AFTER_ROWS", "0"))
    pipeline = os.environ["PW_PIPELINE"]

    class S(pw.Schema):
        k: str
        t: int
        v: int

    # the kill trigger counts BOTH processes' outputs: row ownership is
    # hash-routed, so any single process may legitimately own zero rows
    phase_outs = [
        base / f"out{p}_{os.environ['PW_PHASE']}.jsonl" for p in range(2)
    ]

    rows = pw.io.jsonlines.read(str(in_dir), schema=S, mode="streaming")
    if pipeline == "groupby_sum":
        r = rows.groupby(rows.k).reduce(
            rows.k,
            s=pw.reducers.sum(rows.v),
            mx=pw.reducers.max(rows.v),
            cnt=pw.reducers.count(),
        )
    elif pipeline == "windowby":
        r = rows.windowby(
            rows.t,
            window=pw.temporal.tumbling(duration=4),
            instance=rows.k,
            behavior=pw.temporal.common_behavior(
                delay=2, cutoff=100, keep_results=True
            ),
        ).reduce(
            k=pw.this._pw_instance,
            start=pw.this._pw_window_start,
            cnt=pw.reducers.count(),
            s=pw.reducers.sum(pw.this.v),
        )
    else:
        raise SystemExit(f"unknown pipeline {pipeline}")
    pw.io.jsonlines.write(r, str(out_file))

    def watch():
        while True:
            time.sleep(0.05)
            n = 0
            for p in phase_outs:
                try:
                    n += sum(1 for _ in open(p))
                except OSError:
                    pass
            if die_after and n >= die_after:
                os._exit(17)
            if stop_file.exists():
                rt = pw.internals.parse_graph.G.runtime
                if rt is not None:
                    rt.stop()
                return

    threading.Thread(target=watch, daemon=True).start()
    cfg = pw.persistence.Config.simple_config(
        pw.persistence.Backend.filesystem(str(pdir)),
        snapshot_every=int(os.environ.get("PW_SNAPSHOT_EVERY", "8")),
    )
    pw.run(persistence_config=cfg, autocommit_duration_ms=20)
    print("CLEAN-EXIT", flush=True)
    """
)


def _fold_keyed(paths, key_fields):
    from pathway_tpu.testing.chaos import fold_diff_stream

    return fold_diff_stream(paths, key_fields)


def _run_matrix_kill_restart(tmp_path, pipeline, key_fields, expected, live_expected=None):
    """Shared 2-process kill/restart driver: phase 1 kills pid 1
    mid-stream (pid 0 fail-stops at the next barrier), phase 2 restarts
    the whole group from persistence and must converge on the exact
    merged state of an uninterrupted run."""
    base = tmp_path / "work"
    for pid in range(2):
        (base / f"in{pid}").mkdir(parents=True)
    script = tmp_path / "worker.py"
    script.write_text(_DCN_MATRIX_WORKER)
    port = _free_dcn_port()

    def write_rows(pid, fname, rows):
        with open(base / f"in{pid}" / fname, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    def phase(n, extra):
        return _spawn_group(
            script,
            2,
            port,
            extra_env=lambda pid: {
                "PW_TEST_DIR": str(base),
                "PW_PIPELINE": pipeline,
                **extra(pid),
            },
            timeout=120,
        )

    yield write_rows

    procs, outs = phase(
        1,
        lambda pid: {
            "PW_PHASE": "1",
            **({"PW_DIE_AFTER_ROWS": "2"} if pid == 1 else {}),
        },
    )
    assert procs[1].returncode == 17, outs[1][-2000:]
    assert procs[0].returncode != 0, outs[0][-2000:]

    yield write_rows

    import threading

    all_outs = [
        base / f"out{pid}_{ph}.jsonl" for pid in range(2) for ph in (1, 2)
    ]
    target = live_expected if live_expected is not None else expected

    def stopper():
        deadline = time.time() + 70
        while time.time() < deadline:
            merged = _fold_keyed(all_outs, key_fields)
            if live_expected is not None:
                merged = {
                    k: v for k, v in merged.items() if k in live_expected
                }
            if merged == target:
                break
            time.sleep(0.2)
        (base / "STOP").touch()

    st = threading.Thread(target=stopper, daemon=True)
    st.start()
    procs2, outs2 = phase(2, lambda pid: {"PW_PHASE": "2"})
    st.join(timeout=90)
    for pid, (p, out) in enumerate(zip(procs2, outs2)):
        assert p.returncode == 0, f"phase2 pid={pid}:\n{out[-3000:]}"
        assert "CLEAN-EXIT" in out
    assert _fold_keyed(all_outs, key_fields) == expected


def test_two_process_groupby_sum_kill_restart(tmp_path):
    """Kill/restart matrix, 2-process groupby with sum/max reducers: a
    mid-stream kill + full-group restart recovers from the persisted
    snapshots and the merged totals are exact."""
    rows1 = {
        0: [
            {"k": "x", "t": 0, "v": 3},
            {"k": "y", "t": 1, "v": 5},
            {"k": "x", "t": 2, "v": 4},
        ],
        1: [
            {"k": "y", "t": 0, "v": 2},
            {"k": "z", "t": 1, "v": 7},
            {"k": "x", "t": 2, "v": 1},
        ],
    }
    rows2 = {
        0: [{"k": "z", "t": 3, "v": 10}],
        1: [{"k": "x", "t": 3, "v": 6}],
    }
    # (cnt, mx, s) per key over ALL rows
    expected = {
        ("x",): (4, 6, 14),
        ("y",): (2, 5, 7),
        ("z",): (2, 10, 17),
    }
    gen = _run_matrix_kill_restart(
        tmp_path, "groupby_sum", ["k"], expected
    )
    write_rows = next(gen)
    for pid, rows in rows1.items():
        write_rows(pid, "f1.jsonl", rows)
    write_rows = next(gen)
    for pid, rows in rows2.items():
        write_rows(pid, "f2.jsonl", rows)
    for _ in gen:
        pass


def test_two_process_windowby_behavior_kill_restart(tmp_path):
    """Kill/restart matrix, 2-process windowby + common_behavior: the
    Buffer/Forget watermark state and window aggregates survive a
    mid-stream kill + group restart; merged final windows match the full
    input's window aggregation exactly."""
    rows1 = {
        0: [{"k": "a", "t": t, "v": t} for t in (0, 1, 3, 5, 6)],
        1: [{"k": "b", "t": t, "v": 2 * t} for t in (2, 4, 7)],
    }
    # phase 2 ends with high sentinel times on both processes so every
    # earlier window crosses the delay threshold group-wide
    rows2 = {
        0: [{"k": "a", "t": 9, "v": 9}, {"k": "a", "t": 40, "v": 0}],
        1: [{"k": "b", "t": 11, "v": 22}, {"k": "b", "t": 41, "v": 0}],
    }
    expected = {
        ("a", 0): (3, 4),
        ("a", 4): (2, 11),
        ("a", 8): (1, 9),
        ("a", 40): (1, 0),
        ("b", 0): (1, 4),
        ("b", 4): (2, 22),
        ("b", 8): (1, 22),
        ("b", 40): (1, 0),
    }
    # sentinel windows flush only on clean shutdown; converge on the rest
    live_expected = {k: v for k, v in expected.items() if k[1] < 40}
    gen = _run_matrix_kill_restart(
        tmp_path, "windowby", ["k", "start"], expected, live_expected
    )
    write_rows = next(gen)
    for pid, rows in rows1.items():
        write_rows(pid, "f1.jsonl", rows)
    write_rows = next(gen)
    for pid, rows in rows2.items():
        write_rows(pid, "f2.jsonl", rows)
    for _ in gen:
        pass


_DCN_JOIN = textwrap.dedent(
    """
    import os, json
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    pid = int(os.environ["PATHWAY_PROCESS_ID"])

    class L(pw.Schema):
        k: int
        a: int

    class R(pw.Schema):
        k: int
        b: int

    # left rows split across processes; right table only on process 0 —
    # the exchange must co-locate matching rows regardless of origin
    lrows = [(i % 5, i) for i in range(40) if i % 2 == pid]
    rrows = [(i, i * 100) for i in range(5)] if pid == 0 else []
    lt = pw.debug.table_from_rows(L, lrows)
    rt = pw.debug.table_from_rows(R, rrows)
    j = lt.join(rt, lt.k == rt.k).select(lt.a, rt.b)
    keys, cols = pw.debug.table_to_dicts(j)
    out = sorted((cols["a"][k], cols["b"][k]) for k in keys)
    rtm = pw.internals.parse_graph.G.last_runtime
    from pathway_tpu.engine.dcn import DcnJoinExec
    js = [e for e in rtm.execs.values() if isinstance(e, DcnJoinExec)]
    assert js, "expected a DCN join exec"
    print("RESULT " + json.dumps(out), flush=True)
    """
)


def test_two_process_join_dcn(tmp_path):
    """2-process equijoin: both sides exchanged by join-key hash so
    matches co-locate; union of per-process outputs equals the
    single-process join."""
    script = tmp_path / "worker.py"
    script.write_text(_DCN_JOIN)
    procs, outs = _spawn_group(script, 2, _free_dcn_port())
    results = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"pid={pid} failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results.append(json.loads(line[len("RESULT "):]))
    merged = sorted(tuple(x) for r in results for x in r)
    expected = sorted((i, (i % 5) * 100) for i in range(40))
    assert merged == expected


@pytest.mark.parametrize("wire_fmt", ["codec", "pickle"])
def test_two_process_wordcount_wire_formats(tmp_path, wire_fmt):
    """The PWHX7 columnar codec and the pickle escape hatch produce
    IDENTICAL results end-to-end: same per-process ownership contract,
    same merged totals (acceptance: differential 2-process run with
    PATHWAY_DCN_WIRE=codec vs =pickle)."""
    script = tmp_path / "worker.py"
    script.write_text(_DCN_WORDCOUNT)
    procs, outs = _spawn_group(
        script,
        2,
        _free_dcn_port(),
        extra_env=lambda pid: {"PATHWAY_DCN_WIRE": wire_fmt},
    )
    results = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"pid={pid} failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results.append(json.loads(line[len("RESULT "):]))
    assert len(results) == 2
    assert not (set(results[0]) & set(results[1]))
    merged: dict[str, int] = {}
    for r in results:
        merged.update(r)
    expected = {
        f"w{j}": len([i for i in range(100) if i % 7 == j]) for j in range(7)
    }
    assert merged == expected


def test_host_mesh_rejects_unauthenticated_frames(monkeypatch):
    """A client without the per-job PATHWAY_DCN_SECRET must not get its
    bytes anywhere near pickle.loads (ADVICE r4: pickle over TCP is RCE
    without authentication)."""
    import pickle
    import struct
    import threading

    from pathway_tpu.parallel import host_exchange as hx

    monkeypatch.setenv("PATHWAY_DCN_SECRET", "mesh-auth-test")
    base = _free_port()
    meshes = [None, None]

    def build(pid):
        meshes[pid] = hx.HostMesh(2, pid, base, connect_timeout=30.0)

    threads = [threading.Thread(target=build, args=(pid,)) for pid in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    m0, m1 = meshes
    assert m0 is not None and m1 is not None
    try:
        # rogue client: reads the challenge but answers with a garbage MAC
        rogue_payload = ("data", 1, "evil", 0, "boom")
        body = pickle.dumps(rogue_payload)
        rogue = socket.create_connection(("127.0.0.1", base), timeout=5)
        rogue.settimeout(5)
        nonce = rogue.recv(hx._NONCE_LEN)
        assert len(nonce) == hx._NONCE_LEN
        rogue.sendall(hx._HELLO_MAGIC + struct.pack("<ii", 1, 0) + b"\0" * hx._MAC_LEN)
        rogue.sendall(struct.pack("<I", len(body)) + b"\0" * hx._MAC_LEN + body)
        rogue.close()
        # legitimate traffic still flows
        m0.send(1, "ch", 0, {"ok": True})
        got = m1.gather("ch", 0, timeout=30)
        assert got == {0: {"ok": True}}
        time.sleep(0.3)
        assert ("evil", 0) not in m1._data and ("evil", 0) not in m0._data
        # a mesh without the secret refuses to construct at all
        monkeypatch.delenv("PATHWAY_DCN_SECRET")
        with pytest.raises(hx.HostMeshError, match="PATHWAY_DCN_SECRET"):
            hx.HostMesh(2, 0, _free_port())
    finally:
        m0.close()
        m1.close()


# ---------------------------------------------------------------------------
# Phoenix Mesh chaos matrix (Fault Forge, PR 8)

_DCN_CHAOS_WORKER = textwrap.dedent(
    """
    import os, sys, json, time, pathlib, threading
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    pid = int(os.environ["PATHWAY_PROCESS_ID"])
    inc = os.environ.get("PATHWAY_MESH_INCARNATION", "0")
    base = pathlib.Path(os.environ["PW_TEST_DIR"])
    in_dir = base / f"in{pid}"
    pdir = base / f"pstorage{pid}"
    out_file = base / f"out{pid}_inc{inc}.jsonl"
    stop_file = base / "STOP"

    class S(pw.Schema):
        k: str
        t: int
        v: int

    rows = pw.io.jsonlines.read(str(in_dir), schema=S, mode="streaming")
    r = rows.groupby(rows.k).reduce(
        rows.k,
        s=pw.reducers.sum(rows.v),
        cnt=pw.reducers.count(),
    )
    pw.io.jsonlines.write(r, str(out_file))

    def watch():
        while True:
            time.sleep(0.05)
            if stop_file.exists():
                rt = pw.internals.parse_graph.G.runtime
                if rt is not None:
                    rt.stop()
                return

    threading.Thread(target=watch, daemon=True).start()
    cfg = pw.persistence.Config.simple_config(
        pw.persistence.Backend.filesystem(str(pdir)),
        snapshot_every=int(os.environ.get("PW_SNAPSHOT_EVERY", "2")),
    )
    pw.run(persistence_config=cfg, autocommit_duration_ms=20)
    drv = getattr(pw.internals.parse_graph.G.runtime, "persistence_driver", None)
    print("REPLAYED %d" % (drv.replayed_events if drv else -1), flush=True)
    print("CLEAN-EXIT", flush=True)
    """
)


def test_two_process_kill_mid_tick_supervised_recovery(tmp_path):
    """ACCEPTANCE (Phoenix Mesh): Fault Forge kills rank 1 at the tail
    of a data tick (processed but uncommitted — the group-visible
    mid-tick death); the survivor fail-stops, the GroupSupervisor
    restarts the WHOLE group, incarnation 1 restores the latest
    group-committed snapshot generation + log tail and converges on
    output identical to an uninterrupted run."""
    import threading

    from pathway_tpu.parallel.supervisor import GroupSupervisor
    from pathway_tpu.testing import faults as faults_mod

    base = tmp_path / "work"
    for pid in range(2):
        (base / f"in{pid}").mkdir(parents=True)
    script = tmp_path / "worker.py"
    script.write_text(_DCN_CHAOS_WORKER)
    port = _free_dcn_port()

    def write_rows(pid, fname, rows):
        with open(base / f"in{pid}" / fname, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    # trickle input so incarnation 0 sees several data ticks before the
    # injected death: the first file is written up front, the rest only
    # AFTER the group's first output appears (workers boot slowly — a
    # pre-written pile would collapse into one tick)
    all_rows = {0: [], 1: []}

    def trickler():
        def rows_for(i, pid):
            return [
                {"k": f"k{(i + j + pid) % 4}", "t": i, "v": i + j}
                for j in range(3)
            ]

        for pid in range(2):
            write_rows(pid, "f0.jsonl", rows_for(0, pid))
        deadline = time.time() + 90
        while time.time() < deadline:
            if any(
                p.stat().st_size > 0
                for p in base.glob("out*_inc0.jsonl")
            ):
                break
            time.sleep(0.2)
        for i in range(1, 6):
            for pid in range(2):
                write_rows(pid, f"f{i}.jsonl", rows_for(i, pid))
            time.sleep(0.4)

    # rows are deterministic: precompute them (and the expected fold)
    # without racing the writer thread
    for i in range(6):
        for pid in range(2):
            all_rows[pid].extend(
                {"k": f"k{(i + j + pid) % 4}", "t": i, "v": i + j}
                for j in range(3)
            )
    expected: dict = {}
    for pid in range(2):
        for r in all_rows[pid]:
            cnt, s = expected.get((r["k"],), (0, 0))
            expected[(r["k"],)] = (cnt + 1, s + r["v"])
    all_rows = {0: [], 1: []}  # reset: the trickler re-derives them

    out_paths = lambda: sorted(base.glob("out*_inc*.jsonl"))  # noqa: E731

    def stopper():
        deadline = time.time() + 120
        while time.time() < deadline:
            if _fold_keyed(out_paths(), ["k"]) == expected:
                break
            time.sleep(0.25)
        (base / "STOP").touch()

    tr = threading.Thread(target=trickler, daemon=True)
    st = threading.Thread(target=stopper, daemon=True)
    sup = GroupSupervisor(
        [sys.executable, str(script)],
        2,
        env={
            "PW_TEST_DIR": str(base),
            "PATHWAY_DCN_PORT": str(port),
            "PATHWAY_DCN_SECRET": f"chaos-secret-{port}",
            "PATHWAY_DCN_TIMEOUT": "60",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
            "PATHWAY_FAULTS": "kill=tick:3,pid:1,at:tail",
        },
        max_restarts=2,
        backoff_s=0.1,
        log_dir=str(base / "logs"),
    )
    tr.start()
    st.start()
    rc = sup.run()
    st.join(timeout=150)
    tr.join(timeout=10)
    logs = "\n".join(
        f"--- {p.name}\n{p.read_text()[-2000:]}"
        for p in sorted((base / "logs").glob("*.log"))
    )
    assert rc == 0, logs
    assert sup.restarts_used >= 1, sup.events
    died = [d for _t, k, d in sup.events if k == "rank-died"]
    assert any(
        f"exited {faults_mod.FAULT_EXIT}" in d for d in died
    ), sup.events
    assert _fold_keyed(out_paths(), ["k"]) == expected, logs


def test_two_process_torn_manifest_recovery(tmp_path):
    """Fault Forge torn snapshot on rank 0 (death between segment
    writes and the metadata commit at a group-safe snapshot point): the
    group fail-stops, a clean restart restores the previous consistent
    generation on rank 0 / the group-min on rank 1, and the merged
    totals equal the uninterrupted run."""
    base = tmp_path / "work"
    for pid in range(2):
        (base / f"in{pid}").mkdir(parents=True)
    script = tmp_path / "worker.py"
    script.write_text(_DCN_MATRIX_WORKER)
    port = _free_dcn_port()

    def write_rows(pid, fname, rows):
        with open(base / f"in{pid}" / fname, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    rows1 = {
        0: [{"k": "x", "t": i, "v": i} for i in range(5)],
        1: [{"k": "y", "t": i, "v": 2 * i} for i in range(5)],
    }
    for pid, rows in rows1.items():
        write_rows(pid, "f1.jsonl", rows)

    def phase(extra):
        return _spawn_group(
            script,
            2,
            port,
            extra_env=lambda pid: {
                "PW_TEST_DIR": str(base),
                "PW_PIPELINE": "groupby_sum",
                "PW_SNAPSHOT_EVERY": "1",
                **extra(pid),
            },
            timeout=120,
        )

    from pathway_tpu.testing import faults as faults_mod

    import threading

    # the group-safe snapshot fires at the HEAD of the 2nd data tick
    # (snapshot_every=1): feed a second batch only once the first tick's
    # output is visible, so the torn directive deterministically hits
    # that snapshot's metadata commit on rank 0
    phase1_outs = [base / f"out{p}_1.jsonl" for p in range(2)]

    def feed_second_tick():
        deadline = time.time() + 90
        while time.time() < deadline:
            if _fold_keyed(phase1_outs, ["k"]):
                break
            time.sleep(0.2)
        for pid in range(2):
            write_rows(
                pid, "f1b.jsonl", [{"k": "w", "t": 6 + pid, "v": 1}]
            )

    feeder = threading.Thread(target=feed_second_tick, daemon=True)
    feeder.start()
    procs, outs = phase(
        lambda pid: {
            "PW_PHASE": "1",
            **(
                {"PATHWAY_FAULTS": "torn=nth:1,pid:0"} if pid == 0 else {}
            ),
        }
    )
    feeder.join(timeout=10)
    assert procs[0].returncode == faults_mod.FAULT_EXIT, outs[0][-2000:]
    assert procs[1].returncode != 0, outs[1][-2000:]

    rows2 = {
        0: [{"k": "x", "t": 9, "v": 100}],
        1: [{"k": "z", "t": 9, "v": 7}],
    }
    for pid, rows in rows2.items():
        write_rows(pid, "f2.jsonl", rows)
    # (cnt, mx, s) per key over ALL rows — matrix worker emits cnt/mx/s;
    # "w" is the second-tick trigger batch (one v=1 row per rank)
    expected = {
        ("x",): (6, 100, 110),
        ("y",): (5, 8, 20),
        ("w",): (2, 1, 2),
        ("z",): (1, 7, 7),
    }

    import threading

    all_outs = [
        base / f"out{pid}_{ph}.jsonl" for pid in range(2) for ph in (1, 2)
    ]

    def stopper():
        deadline = time.time() + 70
        while time.time() < deadline:
            if _fold_keyed(all_outs, ["k"]) == expected:
                break
            time.sleep(0.2)
        (base / "STOP").touch()

    st = threading.Thread(target=stopper, daemon=True)
    st.start()
    procs2, outs2 = phase(lambda pid: {"PW_PHASE": "2"})
    st.join(timeout=90)
    for pid, (p, out) in enumerate(zip(procs2, outs2)):
        assert p.returncode == 0, f"phase2 pid={pid}:\n{out[-3000:]}"
        assert "CLEAN-EXIT" in out
    assert _fold_keyed(all_outs, ["k"]) == expected


def test_two_process_duplicated_frame_is_idempotent(tmp_path):
    """Fault Forge duplicates a groupby exchange frame on each rank:
    delivery is keyed per (channel, tick, src), so the duplicate is
    absorbed and the merged wordcount is EXACTLY the uninterrupted
    result — no double-counted rows."""
    script = tmp_path / "worker.py"
    script.write_text(_DCN_WORDCOUNT)
    procs, outs = _spawn_group(
        script,
        2,
        _free_dcn_port(),
        extra_env=lambda pid: {
            "PATHWAY_FAULTS": "dup=ch:gb,nth:1,inc:*"
        },
    )
    results = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"pid={pid} failed:\n{out[-3000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results.append(json.loads(line[len("RESULT "):]))
    assert len(results) == 2
    assert not (set(results[0]) & set(results[1]))
    merged: dict[str, int] = {}
    for r in results:
        merged.update(r)
    expected = {
        f"w{j}": len([i for i in range(100) if i % 7 == j]) for j in range(7)
    }
    assert merged == expected


# ---------------------------------------------------------------------------
# Replica Shield chaos leg: writer + 2 subprocess replicas + router, with a
# Fault-Forge replica kill and a supervised (incarnation-gated) restart.



@pytest.mark.slow
def test_replica_shield_chaos_kill_and_supervised_restart(tmp_path):
    """Full replication chaos leg: a real writer pipeline streams deltas
    to two subprocess replicas behind the failover router; Fault Forge
    kills replica 1 after its 12th applied tick; its Phoenix-Mesh
    supervisor restarts it (incarnation 1 runs fault-free), it
    re-hydrates + replays, and the router re-admits it — while the
    client-visible error count stays zero."""
    import secrets
    import threading

    import requests

    from pathway_tpu.parallel.supervisor import GroupSupervisor
    from pathway_tpu.serving.router import FailoverRouter
    from pathway_tpu.testing import faults

    base = tmp_path
    (base / "docs").mkdir()
    (base / "q").mkdir()
    DIM = 16
    repl_port = _free_port()
    http_ports = [_free_port(), _free_port()]
    secret = secrets.token_hex(16)
    env_common = {
        "PW_WRITER_DIR": str(base),
        "PATHWAY_DCN_SECRET": secret,
        "PATHWAY_REPLICA_DIM": str(DIM),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ),
    }

    def write_docs(lo, hi, tag):
        with open(base / "docs" / f"{tag}.jsonl", "w") as f:
            for i in range(lo, hi):
                f.write(json.dumps({"text": f"doc {i}"}) + "\n")

    write_docs(0, 8, "f0")
    from pathway_tpu.testing.chaos import REPL_WRITER_SCRIPT

    script = base / "writer.py"
    script.write_text(REPL_WRITER_SCRIPT)
    writer_env = dict(os.environ)
    writer_env.update(env_common)
    writer_env["PATHWAY_REPL_PORT"] = str(repl_port)
    writer = subprocess.Popen(
        [sys.executable, str(script)],
        env=writer_env,
        stdout=open(base / "writer.log", "wb"),
        stderr=subprocess.STDOUT,
    )
    sups: list[GroupSupervisor] = []
    sup_threads: list = []
    router = None
    try:
        # wait for the writer's delta stream port to answer
        deadline = time.monotonic() + 120
        up = False
        while time.monotonic() < deadline:
            s = socket.socket()
            try:
                s.connect(("127.0.0.1", repl_port))
                up = True
                break
            except OSError:
                time.sleep(0.5)
            finally:
                s.close()
        assert up, (base / "writer.log").read_text()[-3000:]

        # two supervised replicas; replica 1 carries the fault spec
        for rid in range(2):
            renv = dict(env_common)
            renv["PATHWAY_REPLICA_ID"] = str(rid)
            renv["PATHWAY_REPLICA_STORE"] = str(base / "pstorage")
            renv["PATHWAY_REPL_PORT"] = str(repl_port)
            renv["PATHWAY_REPLICA_HTTP_PORT"] = str(http_ports[rid])
            if rid == 1:
                renv["PATHWAY_FAULTS"] = "kill=replica:1,tick:12"
            sup = GroupSupervisor(
                [sys.executable, "-m", "pathway_tpu.serving.replica"],
                1,
                env=renv,
                max_restarts=2,
                backoff_s=0.2,
                log_dir=str(base / f"replica{rid}-logs"),
            )
            sups.append(sup)
            th = threading.Thread(target=sup.run, daemon=True)
            sup_threads.append(th)
            th.start()

        router = FailoverRouter(
            [f"http://127.0.0.1:{p}" for p in http_ports],
            health_interval_ms=150,
        ).start()
        failures: list = []
        router.add_failure_listener(
            lambda name, why: failures.append((name, why))
        )

        def health(rid):
            try:
                return requests.get(
                    f"http://127.0.0.1:{http_ports[rid]}/replica/health",
                    timeout=2,
                ).json()
            except Exception:
                return None

        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            hs = [health(0), health(1)]
            if all(h is not None and h["ready"] for h in hs):
                break
            time.sleep(0.5)
        else:
            pytest.fail(f"replicas never became ready: {hs}")

        # drive load while trickling docs so replica 1 accumulates
        # applied ticks toward its injected death
        statuses: dict = {}
        url = f"http://127.0.0.1:{router.port}/query"
        killed_seen = restarted_ready = False
        for i in range(200):
            if i % 4 == 0:
                write_docs(8 + i, 9 + i, f"t{i}")
            try:
                r = requests.post(
                    url, json={"query": f"doc {i % 8}", "k": 1}, timeout=15
                )
                statuses[r.status_code] = statuses.get(r.status_code, 0) + 1
            except Exception:
                statuses["transport"] = statuses.get("transport", 0) + 1
            if failures and not killed_seen:
                killed_seen = True
            h1 = health(1)
            if (
                killed_seen
                and h1 is not None
                and h1.get("incarnation") == 1
                and h1.get("ready")
            ):
                restarted_ready = True
                break
            time.sleep(0.15)

        assert killed_seen, "router never observed the replica death"
        assert restarted_ready, (
            "restarted replica never became ready again",
            health(1),
            statuses,
        )
        # the kill was the injected one, and the supervisor restarted it
        assert sups[1].restarts_used >= 1
        died = [e for e in sups[1].events if e[1] == "rank-died"]
        assert died and f"exited {faults.FAULT_EXIT}" in died[0][2]
        # client-visible contract: shed only explicitly, NEVER an error
        errors = sum(
            v
            for k, v in statuses.items()
            if k not in (200, 429, 503)
        )
        assert errors == 0, statuses
        assert statuses.get(200, 0) > 0, statuses
        # the router re-admitted the restarted replica
        ep1 = router.endpoints[1]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and ep1.ejected:
            time.sleep(0.2)
        assert not ep1.ejected
    finally:
        (base / "STOP").touch()
        if router is not None:
            router.stop()
        for sup in sups:
            sup.stop()
        for th in sup_threads:
            th.join(timeout=30)
        writer.terminate()
        try:
            writer.wait(timeout=30)
        except subprocess.TimeoutExpired:
            writer.kill()


# ---------------------------------------------------------------------------
# mesh teardown determinism (the wordcount wire-format flake fix)


def test_mesh_atexit_flush_hook():
    """The atexit hook flush-closes the mesh singleton exactly once:
    the PR-6 overlapped sender means a rank can complete its final
    barrier while its own frame still sits in an outbox — interpreter
    exit used to kill the sender mid-queue and the peer EOF'd
    (test_two_process_wordcount_wire_formats under load).  close()
    queues the stop sentinel BEHIND pending frames, so registering it
    at exit makes the teardown deterministic."""
    from pathway_tpu.parallel import host_exchange as hx

    class _Stub:
        def __init__(self):
            self._closed = False
            self.closes = 0

        def close(self):
            self.closes += 1
            self._closed = True

    stub = _Stub()
    old = hx._mesh
    try:
        hx._mesh = stub
        hx._flush_mesh_at_exit()
        assert stub.closes == 1
        hx._flush_mesh_at_exit()  # already closed: no double close
        assert stub.closes == 1
        hx._mesh = None
        hx._flush_mesh_at_exit()  # no mesh: no-op
    finally:
        hx._mesh = old


def test_mesh_close_delivers_queued_frames(monkeypatch):
    """What the atexit hook relies on: frames already queued on an
    outbox are ON THE WIRE before close() returns — the stop sentinel
    queues behind them."""
    import threading

    from pathway_tpu.parallel import host_exchange as hx

    monkeypatch.setenv("PATHWAY_DCN_SECRET", "flush-test")
    base = _free_port()
    meshes = [None, None]

    def build(pid):
        meshes[pid] = hx.HostMesh(2, pid, base, connect_timeout=30.0)

    threads = [
        threading.Thread(target=build, args=(pid,)) for pid in (0, 1)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    m0, m1 = meshes
    assert m0 is not None and m1 is not None
    try:
        for i in range(8):
            m0.send(1, "flushch", i, {"i": i})
        m0.close()  # what the atexit hook calls
        # every queued frame arrived despite the immediate close
        for i in range(8):
            got = m1.gather("flushch", i, timeout=30)
            assert got == {0: {"i": i}}
    finally:
        m1.close()
