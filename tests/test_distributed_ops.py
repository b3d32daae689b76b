"""Cross-process exchange coverage for EVERY stateful operator type:
a 2-process group and a 1-process run execute the
same pipelines over the same (sharded) inputs; the union of per-process
outputs must equal the single-process result exactly — keys included,
since row keys are value hashes and identical on every process
(reference: the universal Exchange pact moves every operator's rows
between timely workers, external/timely-dataflow/timely/src/dataflow/
channels/pact.rs:56-59; src/engine/dataflow/operators.rs:415 Reshard)."""

from __future__ import annotations

import json
import os
import random
import socket
import subprocess
import sys
import textwrap

import pytest

# every op section prints one "RESULT <tag> <json>" line; rows shard
# round-robin by PATHWAY_PROCESS_ID so each process feeds a disjoint slice
_OPS_WORKER = textwrap.dedent(
    """
    import json, os
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    N = int(os.environ.get("PATHWAY_PROCESSES", "1"))
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))
    G = pw.internals.parse_graph.G

    def mine(rows):
        # keep the GLOBAL row index as an explicit primary key: row keys
        # are then value hashes identical across any process count
        return [(i, *r) for i, r in enumerate(rows) if i % N == PID]

    def emit(tag, table):
        keys, cols = pw.debug.table_to_dicts(table)
        names = sorted(cols)
        out = {str(k): [str(cols[c].get(k)) for c in names] for k in keys}
        print("RESULT " + tag + " " + json.dumps(out, sort_keys=True),
              flush=True)

    # --- deduplicate (route by instance hash) ---------------------------
    G.clear()
    class SD(pw.Schema):
        idx: int = pw.column_definition(primary_key=True)
        v: int
        inst: int
    t = pw.debug.table_from_rows(SD, mine([(i, i % 3) for i in range(20)]))
    emit("dedup", t.deduplicate(
        value=t.v, instance=t.inst, acceptor=lambda new, old: new > old))

    # --- sort: per-instance chains + instance-less global order ---------
    G.clear()
    class SS(pw.Schema):
        idx: int = pw.column_definition(primary_key=True)
        v: int
        inst: int
    t = pw.debug.table_from_rows(
        SS, mine([((i * 7) % 13, i % 2) for i in range(12)]))
    emit("sort_inst", t.sort(key=t.v, instance=t.inst))
    # aligned join-back: prev/next rows must live on the process feeding
    # the input row, or this multi-input row-wise select sees half a row
    G.clear()
    t = pw.debug.table_from_rows(
        SS, mine([((i * 7) % 13, i % 2) for i in range(12)]))
    s = t.sort(key=t.v, instance=t.inst)
    emit("sort_align", t.select(t.v, t.inst, p=s.prev, nx=s.next))
    G.clear()
    t = pw.debug.table_from_rows(
        SS, mine([((i * 5) % 11, 0) for i in range(10)]))
    emit("sort_global", t.sort(key=t.v))

    # --- update_rows (route both sides by row key) ----------------------
    G.clear()
    class SU(pw.Schema):
        k: int = pw.column_definition(primary_key=True)
        val: str
    l = pw.debug.table_from_rows(
        SU, [r[1:] for r in mine([(i, f"L{i}") for i in range(8)])])
    r = pw.debug.table_from_rows(
        SU, [r[1:] for r in mine([(i, f"R{i}") for i in range(4, 11)])])
    emit("update_rows", l.update_rows(r))

    # --- universe set ops ----------------------------------------------
    G.clear()
    a = pw.debug.table_from_rows(
        SU, [r[1:] for r in mine([(i, f"A{i}") for i in range(10)])])
    b = pw.debug.table_from_rows(
        SU, [r[1:] for r in mine([(i, f"B{i}") for i in range(5, 15)])])
    emit("intersect", a.intersect(b))
    G.clear()
    a = pw.debug.table_from_rows(
        SU, [r[1:] for r in mine([(i, f"A{i}") for i in range(10)])])
    b = pw.debug.table_from_rows(
        SU, [r[1:] for r in mine([(i, f"B{i}") for i in range(5, 15)])])
    emit("difference", a.difference(b))

    # --- ix (route lookups to the pointed-at row's owner) ---------------
    G.clear()
    class SA(pw.Schema):
        name: str = pw.column_definition(primary_key=True)
        genus: str
    class SB(pw.Schema):
        bird: str = pw.column_definition(primary_key=True)
        ref: str
    animals = pw.debug.table_from_rows(
        SA, [r[1:] for r in mine([(f"a{i}", f"g{i}") for i in range(8)])])
    birds = pw.debug.table_from_rows(
        SB, [r[1:] for r in mine([(f"b{i}", f"a{(i * 3) % 8}")
                                  for i in range(8)])])
    birds = birds.with_columns(ptr=animals.pointer_from(birds.ref))
    emit("ix", birds.select(latin=animals.ix(birds.ptr).genus))

    # --- gradual_broadcast (threshold table fed on the LAST process
    #     only: replication must carry it everywhere) --------------------
    G.clear()
    class SV(pw.Schema):
        idx: int = pw.column_definition(primary_key=True)
        v: int
    class ST(pw.Schema):
        tid: int = pw.column_definition(primary_key=True)
        lower: int
        value: int
        upper: int
    data = pw.debug.table_from_rows(SV, mine([(i,) for i in range(30)]))
    thr = pw.debug.table_from_rows(
        ST, [(0, 0, 7, 10)] if PID == N - 1 else [])
    thr_prep = thr
    emit("gbcast", data._gradual_broadcast(
        thr, thr.lower, thr.value, thr.upper))

    # --- windowby + behavior: delay=5 buffers rows, cutoff forgets;
    #     the release watermark must be the GROUP max time --------------
    G.clear()
    class SW(pw.Schema):
        idx: int = pw.column_definition(primary_key=True)
        inst: int
        t: int
        v: int
    rows = [(i % 3, (i * 11) % 40, i) for i in range(60)]
    t = pw.debug.table_from_rows(SW, mine(rows))
    emit("window_behavior", t.windowby(
        t.t,
        window=pw.temporal.tumbling(duration=10),
        instance=t.inst,
        behavior=pw.temporal.common_behavior(delay=5),
    ).reduce(
        pw.this._pw_instance,
        start=pw.this._pw_window_start,
        s=pw.reducers.sum(pw.this.v),
    ))

    # --- iterate (fixpoint centralizes on process 0) --------------------
    G.clear()
    class SI(pw.Schema):
        idx: int = pw.column_definition(primary_key=True)
        v: int
    t = pw.debug.table_from_rows(
        SI, mine([(5,), (7,), (12,), (20,)]))
    res = pw.iterate(
        lambda tab: tab.select(
            tab.idx, v=pw.if_else(tab.v > 10, tab.v - 3, tab.v)),
        tab=t,
    )
    emit("iterate", res)

    print("WORKER-DONE", flush=True)
    """
)

# aligned consumption of a key-preserving iterate result: run in its own
# worker because iterate output universes only align with their input via
# with_universe_of
_ITER_ALIGN_WORKER = textwrap.dedent(
    """
    import json, os
    import jax
    jax.config.update("jax_platforms", "cpu")
    import pathway_tpu as pw

    N = int(os.environ.get("PATHWAY_PROCESSES", "1"))
    PID = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

    class SI(pw.Schema):
        idx: int = pw.column_definition(primary_key=True)
        v: int

    rows = [(i, 5 + 4 * i) for i in range(6)]
    t = pw.debug.table_from_rows(
        SI, [r for i, r in enumerate(rows) if i % N == PID])
    res = pw.iterate(
        lambda tab: tab.select(
            tab.idx, v=pw.if_else(tab.v > 10, tab.v - 3, tab.v)),
        tab=t,
    ).with_universe_of(t)
    final = t.select(orig=t.v, done=res.v)
    keys, cols = pw.debug.table_to_dicts(final)
    out = {str(k): [str(cols[c].get(k)) for c in sorted(cols)] for k in keys}
    print("RESULT iter_align " + json.dumps(out, sort_keys=True), flush=True)
    print("WORKER-DONE", flush=True)
    """
)


def _free_base_port(n: int) -> int:
    for _ in range(50):
        base = random.randint(20000, 40000)
        ok = True
        for off in range(n):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", base + off))
            except OSError:
                ok = False
            finally:
                s.close()
            if not ok:
                break
        if ok:
            return base
    raise RuntimeError("no free port range")


def _run_group(script_path, n, timeout=240):
    port = _free_base_port(n)
    secret = f"ops-test-{port}"
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.update(
            PATHWAY_PROCESSES=str(n),
            PATHWAY_PROCESS_ID=str(pid),
            PATHWAY_DCN_PORT=str(port),
            PATHWAY_DCN_SECRET=secret,
            JAX_PLATFORMS="cpu",
            PYTHONPATH=os.path.dirname(os.path.dirname(__file__)),
        )
        env.pop("XLA_FLAGS", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script_path)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results: dict[str, dict] = {}
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"n={n} pid={pid} failed:\n{out[-4000:]}"
        assert "WORKER-DONE" in out
        for line in out.splitlines():
            if not line.startswith("RESULT "):
                continue
            _r, tag, payload = line.split(" ", 2)
            part = json.loads(payload)
            merged = results.setdefault(tag, {})
            for k, v in part.items():
                assert k not in merged or merged[k] == v, (
                    f"{tag}: key {k} emitted on two processes with "
                    f"different values: {merged[k]} vs {v}"
                )
                merged[k] = v
    return results


def test_two_process_iterate_aligned_consumer(tmp_path):
    script = tmp_path / "iter_align_worker.py"
    script.write_text(_ITER_ALIGN_WORKER)
    single = _run_group(script, 1)
    double = _run_group(script, 2)
    assert double == single and single["iter_align"]


def test_two_process_stateful_ops_match_single_process(tmp_path):
    script = tmp_path / "ops_worker.py"
    script.write_text(_OPS_WORKER)
    single = _run_group(script, 1)
    double = _run_group(script, 2)
    assert set(single) == set(double)
    for tag in sorted(single):
        assert double[tag] == single[tag], (
            f"{tag}: 2-process union != single-process result\n"
            f"single={json.dumps(single[tag], sort_keys=True)[:2000]}\n"
            f"double={json.dumps(double[tag], sort_keys=True)[:2000]}"
        )


# ---------------------------------------------------------------------------
# kill/restart for a newly-exchanged op: deduplicate keeps its accepted
# value per instance across a crash of the whole group (reference recovery
# model: whole-cluster restart from the persisted frontier,
# src/persistence/state.rs:291)

_DEDUP_KILL_WORKER = textwrap.dedent(
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
        sensor: str
        value: int

    t = pw.io.jsonlines.read(str(in_dir), schema=S, mode="streaming")
    d = t.deduplicate(
        value=t.value, instance=t.sensor,
        acceptor=lambda new, old: new > old, name="dedup_max",
    )
    pw.io.jsonlines.write(d, str(out_file))

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


def _run_kill_group(script_path, n, port, extra_env, timeout=120):
    secret = f"dedupkill-{port}"
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.update(
            PATHWAY_PROCESSES=str(n),
            PATHWAY_PROCESS_ID=str(pid),
            PATHWAY_DCN_PORT=str(port),
            PATHWAY_DCN_SECRET=secret,
            JAX_PLATFORMS="cpu",
            PYTHONPATH=os.path.dirname(os.path.dirname(__file__)),
        )
        env.pop("XLA_FLAGS", None)
        env.update(extra_env(pid) or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script_path)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def test_two_process_dedup_kill_restart(tmp_path):
    base = tmp_path / "work"
    for pid in range(2):
        (base / f"in{pid}").mkdir(parents=True)
    script = tmp_path / "worker.py"
    script.write_text(_DEDUP_KILL_WORKER)
    port = _free_base_port(2)

    def write_rows(pid, fname, rows):
        with open(base / f"in{pid}" / fname, "w") as f:
            for sensor, value in rows:
                f.write(json.dumps({"sensor": sensor, "value": value}) + "\n")

    write_rows(0, "f1.jsonl", [("a", 3), ("b", 10), ("a", 7), ("c", 1)])
    write_rows(1, "f1.jsonl", [("b", 2), ("c", 5), ("a", 6), ("d", 4)])

    # phase 1: process 1 dies after 2 emitted rows; the group fail-stops
    procs, outs = _run_kill_group(
        script, 2, port,
        lambda pid: {
            "PW_TEST_DIR": str(base),
            "PW_PHASE": "1",
            **({"PW_DIE_AFTER_ROWS": "2"} if pid == 1 else {}),
        },
    )
    assert procs[1].returncode == 17, outs[1][-2000:]
    assert procs[0].returncode != 0, outs[0][-2000:]

    # phase 2: more input (some values lower — must NOT regress the
    # accepted max), full-group restart from persisted dedup state
    write_rows(0, "f2.jsonl", [("a", 2), ("d", 9)])
    write_rows(1, "f2.jsonl", [("b", 11), ("e", 8)])
    (base / "STOP").parent.mkdir(exist_ok=True)
    import threading
    import time as _time

    def stopper():
        _time.sleep(12)
        (base / "STOP").touch()

    threading.Thread(target=stopper, daemon=True).start()
    procs, outs = _run_kill_group(
        script, 2, _free_base_port(2),
        lambda pid: {"PW_TEST_DIR": str(base), "PW_PHASE": "2"},
    )
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"pid={pid} failed:\n{out[-3000:]}"
        assert "CLEAN-EXIT" in out

    # fold the phase-2 diff streams (dedup state re-emits on restart, so
    # phase 2 alone carries the full final state)
    state: dict[str, int] = {}
    for pid in range(2):
        for line in open(base / f"out{pid}_2.jsonl"):
            o = json.loads(line)
            if o["diff"] > 0:
                state[o["sensor"]] = o["value"]
            elif state.get(o["sensor"]) == o["value"]:
                del state[o["sensor"]]
    assert state == {"a": 7, "b": 11, "c": 5, "d": 9, "e": 8}
