"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest


def _mesh(n=8):
    from pathway_tpu.parallel.mesh import make_mesh

    return make_mesh(n, axis_names=("data",))


def test_sharded_topk_matches_dense():
    import jax.numpy as jnp

    from pathway_tpu.ops.knn import dense_topk, sharded_topk

    mesh = _mesh(8)
    rng = np.random.default_rng(0)
    corpus = rng.normal(size=(64, 16)).astype(np.float32)
    valid = np.ones(64, dtype=bool)
    queries = rng.normal(size=(4, 16)).astype(np.float32)

    s_ref, i_ref = dense_topk(
        jnp.asarray(queries), jnp.asarray(corpus), jnp.asarray(valid), 5,
        metric="cosine", bf16=False,
    )
    s_sh, i_sh = sharded_topk(
        jnp.asarray(queries), jnp.asarray(corpus), jnp.asarray(valid), 5,
        mesh=mesh, metric="cosine", bf16=False,
    )
    assert (np.asarray(i_ref) == np.asarray(i_sh)).all()
    assert np.allclose(np.asarray(s_ref), np.asarray(s_sh), atol=1e-5)


def test_exchange_by_shard():
    from pathway_tpu.parallel.collectives import exchange_by_shard

    mesh = _mesh(8)
    vals = np.arange(32, dtype=np.float32).reshape(16, 2)
    dest = (np.arange(16) % 8).astype(np.int32)
    blocks, counts = exchange_by_shard(vals, dest, mesh)
    assert counts.sum() == 16
    for s in range(8):
        rows = blocks[s, : counts[s]]
        # each shard received exactly the rows addressed to it
        expect = vals[dest == s]
        assert sorted(map(tuple, rows)) == sorted(map(tuple, expect))


def test_ragged_all_to_all_exact():
    """Typed columns survive the exchange bit-for-bit and land on the
    right shard (u64 keys, f64 values, i64 diffs)."""
    from pathway_tpu.parallel.exchange import (
        exchange_rows,
        pack_columns,
        unpack_columns,
    )

    mesh = _mesh(8)
    rng = np.random.default_rng(0)
    n = 1000
    keys = rng.integers(0, 2**63, size=n).astype(np.uint64)
    vals = rng.normal(size=n)
    diffs = rng.choice([-1, 1], size=n).astype(np.int64)
    dest = (keys % 8).astype(np.int32)

    w, spec = pack_columns([keys, vals, diffs])
    k2, v2, d2 = unpack_columns(w, spec)
    assert (k2 == keys).all() and (v2 == vals).all() and (d2 == diffs).all()

    blocks = exchange_rows([keys, vals, diffs], dest, mesh)
    got = {}
    for s, (bk, bv, bd) in enumerate(blocks):
        assert ((bk % 8) == s).all(), f"shard {s} received foreign rows"
        for k, v, d in zip(bk, bv, bd):
            got[int(k)] = (float(v), int(d))
    assert len(got) == len(set(keys.tolist()))
    for k, v, d in zip(keys, vals, diffs):
        assert got[int(k)] == (float(v), int(d))


def test_sharded_knn_index():
    """TpuDenseKnnIndex with a mesh — corpus rows sharded over devices."""
    from pathway_tpu.stdlib.indexing._index_impls import TpuDenseKnnIndex

    mesh = _mesh(8)
    ix = TpuDenseKnnIndex(dimensions=8, mesh=mesh, reserved_space=16)
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(40, 8)).astype(np.float32)
    for i in range(40):
        ix.upsert(i, vecs[i], None)
    res = ix.search([(vecs[7], 3, None)])
    assert res[0][0][0] == 7


def test_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)


# ---------------------------------------------------------------------------
# Engine-level sharding: per-shard state + device exchange


def _with_engine_mesh(n=8):
    from pathway_tpu.parallel import mesh as mesh_mod

    mesh_mod.set_engine_mesh(_mesh(n))
    return mesh_mod


def test_sharded_groupby_matches_single_shard():
    """Same pipeline, sharded vs unsharded engine: identical results, and
    each shard's keyed state is disjoint (the Exchange invariant)."""
    import pathway_tpu as pw
    from pathway_tpu.engine.sharded import ShardedGroupByExec
    from pathway_tpu.internals import parse_graph
    from pathway_tpu.parallel import mesh as mesh_mod

    class S(pw.Schema):
        word: str
        v: int

    rows = [(f"w{i % 17}", i % 5) for i in range(300)]

    def build_and_run():
        t = pw.debug.table_from_rows(S, rows)
        res = t.groupby(t.word).reduce(
            t.word, s=pw.reducers.sum(t.v), c=pw.reducers.count()
        )
        return pw.debug.table_to_dicts(res)

    keys0, cols0 = build_and_run()
    try:
        _with_engine_mesh(8)
        keys1, cols1 = build_and_run()
        rt = parse_graph.G.last_runtime
        sharded_execs = [
            ex
            for ex in rt.execs.values()
            if isinstance(ex, ShardedGroupByExec)
        ]
        assert sharded_execs, "engine mesh set but groupby did not shard"
        owned = sharded_execs[0].shard_group_keys()
        assert sum(len(s) for s in owned) == 17
        for i in range(len(owned)):
            for j in range(i + 1, len(owned)):
                assert not (owned[i] & owned[j]), "shard state overlaps"
        assert rt.frontier_syncs > 0  # frontier all-reduce ran per tick
    finally:
        mesh_mod.set_engine_mesh(None)
    assert sorted(keys0) == sorted(keys1)
    assert cols0 == cols1


def test_sharded_groupby_device_exchange_path():
    """Numeric rows travel through the real device all-to-all."""
    import pathway_tpu as pw
    from pathway_tpu.engine import sharded
    from pathway_tpu.engine.sharded import ShardedGroupByExec
    from pathway_tpu.internals import parse_graph
    from pathway_tpu.parallel import mesh as mesh_mod

    class S(pw.Schema):
        g: int
        v: float

    rows = [(i % 13, float(i) / 7.0) for i in range(600)]

    def build_and_run():
        t = pw.debug.table_from_rows(S, rows)
        res = t.groupby(t.g).reduce(
            t.g, s=pw.reducers.sum(t.v), c=pw.reducers.count()
        )
        return pw.debug.table_to_dicts(res)

    keys0, cols0 = build_and_run()
    old_min = sharded.DEVICE_EXCHANGE_MIN_ROWS
    try:
        sharded.DEVICE_EXCHANGE_MIN_ROWS = 1
        _with_engine_mesh(8)
        keys1, cols1 = build_and_run()
        rt = parse_graph.G.last_runtime
        ex = next(
            e for e in rt.execs.values() if isinstance(e, ShardedGroupByExec)
        )
        assert ex.router.device_exchanges >= 1, (
            "numeric groupby never used the device all-to-all"
        )
    finally:
        sharded.DEVICE_EXCHANGE_MIN_ROWS = old_min
        mesh_mod.set_engine_mesh(None)
    assert sorted(keys0) == sorted(keys1)
    assert cols0 == cols1


def test_sharded_join_matches_single_shard():
    import pathway_tpu as pw
    from pathway_tpu.engine.sharded import ShardedJoinExec
    from pathway_tpu.internals import parse_graph
    from pathway_tpu.parallel import mesh as mesh_mod

    class L(pw.Schema):
        k: str
        a: int

    class R(pw.Schema):
        k: str
        b: int

    lrows = [(f"k{i % 11}", i) for i in range(80)]
    rrows = [(f"k{i % 7}", i * 10) for i in range(40)]

    def build_and_run():
        lt = pw.debug.table_from_rows(L, lrows)
        rt_ = pw.debug.table_from_rows(R, rrows)
        j = lt.join(rt_, lt.k == rt_.k).select(
            lt.k, pw.left.a, pw.right.b
        )
        return pw.debug.table_to_dicts(j)

    keys0, cols0 = build_and_run()
    try:
        _with_engine_mesh(8)
        keys1, cols1 = build_and_run()
        rt = parse_graph.G.last_runtime
        assert any(
            isinstance(e, ShardedJoinExec) for e in rt.execs.values()
        ), "engine mesh set but join did not shard"
    finally:
        mesh_mod.set_engine_mesh(None)
    assert sorted(keys0) == sorted(keys1)
    assert cols0 == cols1


def test_cli_spawn_sets_engine_shards(tmp_path):
    """`pathway-tpu spawn -t N prog` runs the program with an N-shard
    engine mesh instead of redundant copies (reference: PATHWAY_THREADS
    workers, src/engine/dataflow/config.rs:88-121)."""
    import subprocess
    import sys

    prog = tmp_path / "prog.py"
    prog.write_text(
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from pathway_tpu.parallel.mesh import get_engine_mesh\n"
        "em = get_engine_mesh()\n"
        "assert em is not None, 'engine mesh not configured'\n"
        "print('shards:', em[0].shape['data'])\n"
    )
    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "pathway_tpu.cli",
            "spawn",
            "-t",
            "4",
            "--",
            sys.executable,
            str(prog),
        ],
        capture_output=True,
        text=True,
        timeout=120,
        env={
            **{
                k: v
                for k, v in __import__("os").environ.items()
                if k not in ("XLA_FLAGS", "PATHWAY_ENGINE_SHARDS")
            },
            "PYTHONPATH": "/root/repo",
        },
    )
    assert out.returncode == 0, out.stderr
    assert "shards: 4" in out.stdout


def test_sharded_window_matches_single_shard():
    """Per-instance tumbling-window aggregation at 8 engine shards equals
    the unsharded result; the temporal buffer state is spread across
    shards (the reference centralizes postponed rows
    on one worker, time_column.rs:44-47)."""
    import pathway_tpu as pw
    from pathway_tpu.engine.sharded import ShardedBufferExec
    from pathway_tpu.internals import parse_graph

    class S(pw.Schema):
        instance: int
        t: int
        v: int

    rows = [(i % 5, i % 40, i) for i in range(400)]

    def build_and_run():
        t = pw.debug.table_from_rows(S, rows)
        res = t.windowby(
            t.t,
            window=pw.temporal.tumbling(duration=10),
            instance=t.instance,
            behavior=pw.temporal.common_behavior(delay=5),
        ).reduce(
            pw.this._pw_instance,
            start=pw.this._pw_window_start,
            s=pw.reducers.sum(pw.this.v),
        )
        return pw.debug.table_to_dicts(res)

    keys0, cols0 = build_and_run()
    parse_graph.G.clear()
    mesh_mod = _with_engine_mesh(8)
    try:
        keys1, cols1 = build_and_run()
        rt = parse_graph.G.last_runtime
        bufs = [
            e
            for e in rt.execs.values()
            if isinstance(e, ShardedBufferExec)
        ]
        assert bufs, "expected a sharded buffer exec"
        # buffer state was actually SPREAD across shards (held empties
        # after the final flush, so assert on ever-touched keys): disjoint
        # ownership, more than one shard populated
        touched = bufs[0].shard_touched_keys()
        populated = [s for s in touched if s]
        assert len(populated) >= 2, "buffer rows all landed on one shard"
        for i in range(len(touched)):
            for j in range(i + 1, len(touched)):
                assert not (touched[i] & touched[j]), "key on two shards"
        assert sum(cols1["s"].values()) == sum(cols0["s"].values())
        assert dict(cols0["s"]) == dict(cols1["s"])
        assert dict(cols0["start"]) == dict(cols1["start"])
    finally:
        mesh_mod.set_engine_mesh(None)
        parse_graph.G.clear()


def test_sharded_sort_matches_single_shard():
    """Instance-sharded prev/next pointers at 8 shards equal the
    unsharded result; each instance's order lives on exactly one shard."""
    import pathway_tpu as pw
    from pathway_tpu.engine.sharded import ShardedSortExec
    from pathway_tpu.internals import parse_graph

    class S(pw.Schema):
        instance: int
        k: int

    rows = [((i * 7) % 6, (i * 13) % 97) for i in range(200)]

    def build_and_run():
        t = pw.debug.table_from_rows(S, rows)
        res = t.sort(key=t.k, instance=t.instance)
        return pw.debug.table_to_dicts(res)

    keys0, cols0 = build_and_run()
    parse_graph.G.clear()
    mesh_mod = _with_engine_mesh(8)
    try:
        keys1, cols1 = build_and_run()
        rt = parse_graph.G.last_runtime
        sorts = [
            e for e in rt.execs.values() if isinstance(e, ShardedSortExec)
        ]
        assert sorts, "expected a sharded sort exec"
        insts = sorts[0].shard_instances()
        populated = [s for s in insts if s]
        assert len(populated) >= 2, "instances all landed on one shard"
        for i in range(len(insts)):
            for j in range(i + 1, len(insts)):
                assert not (insts[i] & insts[j]), "instance on two shards"
        assert dict(cols0["prev"]) == dict(cols1["prev"])
        assert dict(cols0["next"]) == dict(cols1["next"])
    finally:
        mesh_mod.set_engine_mesh(None)
        parse_graph.G.clear()
