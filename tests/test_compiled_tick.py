"""Tick Forge differential suite: compiled segments (engine/compile.py)
must produce diff-batch streams EQUAL to the interpreter — exact for
int/bool/key/diff columns, allclose for floats — over randomized
insert/retract/update sequences, including graphs whose chains are cut
by fallback boundaries (UDFs, object columns), plus the escape hatch
(PATHWAY_COMPILED_TICK=0 restores the byte-identical interpreter), the
shape-bucketed compilation cache, and the compile-boundary doctor rule.
Oracle pattern as in PR 5/7 (tests/test_state_ledger.py)."""

from __future__ import annotations

import math
import os

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.batch import DiffBatch
from pathway_tpu.engine.expression_eval import InternalColRef
from pathway_tpu.engine.nodes import (
    ConcatNode,
    FilterNode,
    GroupByNode,
    InputNode,
    OutputNode,
    ReindexNode,
    RowwiseNode,
)
from pathway_tpu.engine.reducers import ReducerSpec
from pathway_tpu.engine.runtime import Runtime, StaticSource
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr

# ---------------------------------------------------------------------------
# harness


class _Src(StaticSource):
    def __init__(self, names, ticks):
        super().__init__(names)
        self.ticks = ticks

    def events(self):
        for i, b in enumerate(self.ticks):
            yield i, b


def _ref(name: str) -> InternalColRef:
    return InternalColRef(0, name)


def _run(build, compiled: bool, min_rows: str = "1"):
    """Build a fresh graph via `build(capture)` and run it under the
    requested path; returns (per-tick rows, runtime)."""
    old_tick = os.environ.get("PATHWAY_COMPILED_TICK")
    old_min = os.environ.get("PATHWAY_COMPILED_MIN_ROWS")
    os.environ["PATHWAY_COMPILED_TICK"] = "1" if compiled else "0"
    os.environ["PATHWAY_COMPILED_MIN_ROWS"] = min_rows
    try:
        captured: dict[int, list] = {}

        def capture(t, b):
            rows = captured.setdefault(t, [])
            for k, d, vals in b.iter_rows():
                rows.append((int(k), int(d), tuple(vals)))

        out = build(capture)
        rt = Runtime([out] if not isinstance(out, list) else out)
        rt.run()
        return captured, rt
    finally:
        for k, v in (
            ("PATHWAY_COMPILED_TICK", old_tick),
            ("PATHWAY_COMPILED_MIN_ROWS", old_min),
        ):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _vals_close(a, b) -> bool:
    if isinstance(a, float) or isinstance(b, float) or isinstance(
        a, np.floating
    ) or isinstance(b, np.floating):
        fa, fb = float(a), float(b)
        if math.isnan(fa) and math.isnan(fb):
            return True
        return math.isclose(fa, fb, rel_tol=1e-9, abs_tol=1e-12)
    if isinstance(a, (bool, np.bool_)) or isinstance(b, (bool, np.bool_)):
        return bool(a) == bool(b)
    if isinstance(a, (int, np.integer)) and isinstance(b, (int, np.integer)):
        return int(a) == int(b)
    return a == b


def _assert_streams_equal(got, want):
    """Per-tick equality of the emitted diff streams.  Both paths are
    order-deterministic (maps/filters/concat preserve input order, the
    bulk groupby factorizes by first occurrence), so rows compare
    pairwise; values compare by numeric identity, not representation —
    the compiled path legally returns np scalars where the interpreter
    boxes Python ones."""
    assert set(got) == set(want), (sorted(got), sorted(want))
    for t in sorted(want):
        g, w = got[t], want[t]
        assert len(g) == len(w), f"tick {t}: {len(g)} rows vs {len(w)}"
        for (gk, gd, gv), (wk, wd, wv) in zip(g, w):
            assert gk == wk and gd == wd, f"tick {t}: {gk, gd} vs {wk, wd}"
            assert len(gv) == len(wv)
            for x, y in zip(gv, wv):
                assert _vals_close(x, y), f"tick {t} key {gk}: {x!r} != {y!r}"


def _random_ticks(
    rng, n_ticks=6, rows_per_tick=40, with_floats=True, with_str=False
):
    """Randomized insert/retract/update sequence over int/float/bool
    (and optionally object/string) columns.  Retractions replay an
    earlier row with diff=-1; updates are retract+insert under one key."""
    names = ["a", "b", "flag"] + (["tag"] if with_str else [])
    live: list[tuple[int, tuple]] = []
    ticks = []
    next_key = 0
    for _ in range(n_ticks):
        keys, diffs, rows = [], [], []
        for _ in range(rows_per_tick):
            ins = not live or rng.random() < 0.7
            if ins:
                k = next_key
                next_key += 1
                vals = (
                    int(rng.integers(-1000, 1000)),
                    float(rng.normal()) if with_floats else float(0),
                    bool(rng.integers(0, 2)),
                ) + ((f"tag{int(rng.integers(0, 7))}",) if with_str else ())
                live.append((k, vals))
                keys.append(k)
                diffs.append(1)
                rows.append(vals)
            else:
                i = int(rng.integers(0, len(live)))
                k, vals = live.pop(i)
                keys.append(k)
                diffs.append(-1)
                rows.append(vals)
                if rng.random() < 0.5:  # update: re-insert changed values
                    nv = (vals[0] + 1, vals[1] * 2.0, not vals[2]) + vals[3:]
                    live.append((k, nv))
                    keys.append(k)
                    diffs.append(1)
                    rows.append(nv)
        cols = {}
        for ci, name in enumerate(names):
            vals = [r[ci] for r in rows]
            if name == "a":
                cols[name] = np.array(vals, dtype=np.int64)
            elif name == "b":
                cols[name] = np.array(vals, dtype=np.float64)
            elif name == "flag":
                cols[name] = np.array(vals, dtype=bool)
            else:
                col = np.empty(len(vals), dtype=object)
                col[:] = vals
                cols[name] = col
        ticks.append(
            DiffBatch(
                np.array(keys, dtype=np.uint64),
                np.array(diffs, dtype=np.int64),
                cols,
            )
        )
    return names, ticks


def _segments(rt):
    assert rt.compiled_plan is not None, "expected a compiled plan"
    return rt.compiled_plan.segments


def _compiled_ticks(rt) -> int:
    return sum(s.compiled_ticks for s in _segments(rt))


# ---------------------------------------------------------------------------
# differential: map / filter / reindex / concat chains


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_map_filter_map_chain_differential(seed):
    rng0 = np.random.default_rng(seed)
    names, ticks = _random_ticks(rng0)

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m1 = RowwiseNode(
            [inp],
            {
                "x": _ref("a") * 2 + 1,
                "y": _ref("b") - _ref("a"),
                "flag": _ref("flag"),
            },
        )
        f = FilterNode(
            m1, (_ref("x") > 0) & _ref("flag") | (_ref("y") <= 0.0)
        )
        m2 = RowwiseNode(
            [f],
            {
                "z": expr.IfElseExpression(
                    _ref("flag"), _ref("x"), -_ref("x")
                ),
                "w": expr.CastExpression(dt.FLOAT, _ref("x")) * _ref("y"),
            },
        )
        return OutputNode(m2, capture)

    want, rt0 = _run(build, compiled=False)
    assert rt0.compiled_plan is None  # escape hatch: no planning at all
    got, rt1 = _run(build, compiled=True)
    assert _compiled_ticks(rt1) > 0, "compiled path never dispatched"
    _assert_streams_equal(got, want)


def test_bare_column_predicate_and_keys_compile():
    """Filter predicates and reindex keys that are BARE column refs
    (no expression on top) must still register the column as a device
    input — the untraced entry used to KeyError on first dispatch (or, with nothing else to lower,
    refuse to compile at all as 'constant-only')."""
    rng = np.random.default_rng(11)
    names = ["a", "flag"]
    ticks = []
    for t in range(4):
        n = 32
        ticks.append(
            DiffBatch(
                np.arange(t * n, (t + 1) * n, dtype=np.uint64),
                np.ones(n, dtype=np.int64),
                {
                    # non-negative: reindex keys go through uint64
                    "a": rng.integers(0, 1000, size=n).astype(np.int64),
                    "flag": rng.integers(0, 2, size=n).astype(bool),
                },
            )
        )

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode(
            [inp],
            {"x": _ref("a") * 2, "flag": _ref("flag"), "k": _ref("a")},
        )
        f = FilterNode(m, _ref("flag"))  # bare bool column predicate
        r = ReindexNode(f, _ref("k"))    # bare int64 column keys
        return OutputNode(r, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    assert _compiled_ticks(rt) > 0, "bare-ref chain never compiled"
    _assert_streams_equal(got, want)

    # the pure-passthrough variant: a LONE bare-ref filter is the whole
    # chain — nothing else registers device inputs
    def build_lone(capture):
        inp = InputNode(_Src(names, ticks), names)
        f = FilterNode(inp, _ref("flag"))
        return OutputNode(f, capture)

    want2, _ = _run(build_lone, compiled=False)
    got2, rt2 = _run(build_lone, compiled=True)
    assert _compiled_ticks(rt2) > 0, "lone bare-ref filter never compiled"
    _assert_streams_equal(got2, want2)


@pytest.mark.parametrize("seed", [3, 4])
def test_reindex_chain_differential(seed):
    rng0 = np.random.default_rng(seed)
    names, ticks = _random_ticks(rng0, with_floats=False)

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m1 = RowwiseNode(
            [inp], {"a": _ref("a"), "k2": abs(_ref("a")) * 11 + 5}
        )
        ri = ReindexNode(m1, _ref("k2"))
        m2 = RowwiseNode([ri], {"v": _ref("a") + _ref("k2")})
        return OutputNode(m2, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    assert _compiled_ticks(rt) > 0
    _assert_streams_equal(got, want)


def test_concat_fanin_differential():
    rng0 = np.random.default_rng(7)
    names, ticks_a = _random_ticks(rng0, n_ticks=4)
    _, ticks_b = _random_ticks(rng0, n_ticks=4)
    # disjoint key spaces: shift input B's keys
    ticks_b = [
        DiffBatch(b.keys + np.uint64(1 << 32), b.diffs, b.columns)
        for b in ticks_b
    ]

    def build(capture):
        ia = InputNode(_Src(names, ticks_a), names)
        ib = InputNode(_Src(names, ticks_b), names)
        cc = ConcatNode([ia, ib])
        m = RowwiseNode(
            [cc], {"s": _ref("a") + 1, "b": _ref("b"), "flag": _ref("flag")}
        )
        f = FilterNode(m, _ref("s") >= 0)
        return OutputNode(f, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    assert _compiled_ticks(rt) > 0
    _assert_streams_equal(got, want)


def test_object_column_passes_through_host_side():
    """String columns never cross the device but must ride compiled
    segments untouched (host passthrough with the filter mask applied)."""
    rng0 = np.random.default_rng(11)
    names, ticks = _random_ticks(rng0, with_str=True)

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode(
            [inp], {"x": _ref("a") * 3, "tag": _ref("tag")}
        )
        f = FilterNode(m, _ref("x") > -600)
        return OutputNode(f, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    assert _compiled_ticks(rt) > 0
    _assert_streams_equal(got, want)


# ---------------------------------------------------------------------------
# differential: fallback boundaries


def test_udf_boundary_splits_chain_differential():
    """A pw.apply node in the middle of a chain is NOT lowerable: the
    planner must cut there, the UDF runs interpreted, and the fused
    prefix/suffix still agree with the full interpreter."""
    rng0 = np.random.default_rng(13)
    names, ticks = _random_ticks(rng0)

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m1 = RowwiseNode(
            [inp], {"x": _ref("a") + 7, "b": _ref("b")}
        )
        udf = RowwiseNode(
            [m1],
            {
                "x": _ref("x"),
                "u": expr.ApplyExpression(
                    lambda x: x % 97, dt.INT, False, True, (_ref("x"),), {}
                ),
            },
        )
        m2 = RowwiseNode([udf], {"y": _ref("u") * 2 - _ref("x")})
        f = FilterNode(m2, _ref("y") != 0)
        return OutputNode(f, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    plan = rt.compiled_plan
    assert plan is not None
    # the UDF node itself is in no segment
    udf_nodes = [
        n
        for n in rt.order
        if isinstance(n, RowwiseNode)
        and any(
            isinstance(e, expr.ApplyExpression) for e in n.exprs.values()
        )
    ]
    assert udf_nodes and all(
        plan.segment_of(n.id) is None for n in udf_nodes
    )
    assert _compiled_ticks(rt) > 0
    _assert_streams_equal(got, want)


def test_error_poison_operator_falls_back():
    """Division has interpreter-only poison semantics (record_error +
    per-row Error on zero divisors) — chains containing it must run
    interpreted and still match."""
    names = ["a", "d"]
    ticks = [
        DiffBatch(
            np.arange(4, dtype=np.uint64),
            np.ones(4, dtype=np.int64),
            {
                "a": np.array([10, 20, 30, 40], dtype=np.int64),
                "d": np.array([2, 0, 5, 0], dtype=np.int64),
            },
        )
    ]

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode([inp], {"q": _ref("a") // _ref("d")})
        return OutputNode(m, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    # the whole chain is uncompilable -> no segments at all
    assert rt.compiled_plan is None or all(
        s.compiled_ticks == 0 for s in rt.compiled_plan.segments
    )
    _assert_streams_equal(got, want)
    # the poison contract itself: zero divisors yield ERROR rows, the
    # clean rows the exact quotient
    by_key = {k: v for k, d, v in next(iter(got.values()))}
    from pathway_tpu.internals.api import ERROR

    assert by_key[0] == (5,) and by_key[2] == (6,)
    assert by_key[1] == (ERROR,) and by_key[3] == (ERROR,)


def test_runtime_dtype_fallback_is_negative_cached():
    """Object-dtype values in a structurally compilable chain fall back
    per tick (NotCompilable at lowering) and the (bucket, dtype) key is
    negative-cached so later ticks skip re-tracing."""
    names = ["a"]
    col = np.empty(8, dtype=object)
    col[:] = [1, 2, None, 4, 5, 6, 7, 8]  # None keeps the column object
    tick = DiffBatch(
        np.arange(8, dtype=np.uint64), np.ones(8, dtype=np.int64), {"a": col}
    )
    ticks = [tick, tick, tick]

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode([inp], {"x": _ref("a") * 2})
        f = FilterNode(m, _ref("x") != 4)
        return OutputNode(f, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    segs = _segments(rt)
    assert len(segs) == 1
    assert segs[0].compiled_ticks == 0
    assert segs[0].fallback_ticks == 3
    assert segs[0]._FALLBACK in segs[0]._cache.values()
    _assert_streams_equal(got, want)


def test_min_rows_keeps_tiny_ticks_on_the_interpreter():
    names = ["a"]
    ticks = [
        DiffBatch(
            np.array([i], dtype=np.uint64),
            np.ones(1, dtype=np.int64),
            {"a": np.array([i], dtype=np.int64)},
        )
        for i in range(3)
    ]

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode([inp], {"x": _ref("a") + 1})
        f = FilterNode(m, _ref("x") > 0)
        return OutputNode(f, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True, min_rows="64")
    segs = _segments(rt)
    assert segs[0].compiled_ticks == 0 and segs[0].fallback_ticks == 3
    _assert_streams_equal(got, want)


# ---------------------------------------------------------------------------
# shape-bucketed cache


def test_shape_bucket_cache_reuses_programs():
    """Ticks on the same (bucket, dtype) signature compile once; a new
    row-count bucket adds exactly one cache entry; every dispatch after
    warmup is a hit (the steady-state serving contract)."""
    names = ["a", "b"]

    def tick(n, base):
        return DiffBatch(
            np.arange(base, base + n, dtype=np.uint64),
            np.ones(n, dtype=np.int64),
            {
                "a": np.arange(n, dtype=np.int64),
                "b": np.linspace(0.0, 1.0, n),
            },
        )

    # 6 ticks in the 64-bucket (33..64 rows), then 2 in the 128-bucket
    ticks = [tick(40 + i, 1000 * i) for i in range(6)] + [
        tick(100 + i, 100_000 + 1000 * i) for i in range(2)
    ]

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode([inp], {"x": _ref("a") * 2 + 1, "y": _ref("b") * 0.5})
        f = FilterNode(m, _ref("x") >= 0)
        return OutputNode(f, capture)

    want, _ = _run(build, compiled=False)
    got, rt = _run(build, compiled=True)
    segs = _segments(rt)
    assert len(segs) == 1
    seg = segs[0]
    assert seg.compiled_ticks == 8 and seg.fallback_ticks == 0
    buckets = {k[0] for k in seg._cache}
    assert buckets == {64, 128}
    assert len(seg._cache) == 2  # one program per bucket, none negative
    _assert_streams_equal(got, want)


def test_escape_hatch_env_zero_means_no_planning():
    os.environ["PATHWAY_COMPILED_TICK"] = "0"
    try:
        from pathway_tpu.engine.compile import (
            compiled_tick_enabled,
            plan_segments,
        )

        assert not compiled_tick_enabled()
        assert plan_segments([], {}) is None
    finally:
        os.environ.pop("PATHWAY_COMPILED_TICK", None)


# ---------------------------------------------------------------------------
# groupby semigroup partials (device twin, forced on for the test)


@pytest.mark.parametrize("force_device", ["0", "1"])
def test_groupby_semigroup_partials_differential(force_device):
    rng0 = np.random.default_rng(17)
    names, ticks = _random_ticks(rng0, n_ticks=5, rows_per_tick=120)

    def build(capture):
        inp = InputNode(_Src(names, ticks), names)
        m = RowwiseNode(
            [inp],
            {"g": _ref("a") & 15, "v": _ref("a"), "b": _ref("b")},
        )
        gb = GroupByNode(
            m,
            ["g"],
            {
                "cnt": ReducerSpec(kind="count"),
                "tot": ReducerSpec(kind="sum", arg_cols=("v",)),
                "mean": ReducerSpec(kind="avg", arg_cols=("b",)),
            },
        )
        return OutputNode(gb, capture)

    old = os.environ.get("PATHWAY_COMPILED_GROUPBY")
    os.environ["PATHWAY_COMPILED_GROUPBY"] = force_device
    try:
        want, _ = _run(build, compiled=False)
        got, _rt = _run(build, compiled=True)
    finally:
        if old is None:
            os.environ.pop("PATHWAY_COMPILED_GROUPBY", None)
        else:
            os.environ["PATHWAY_COMPILED_GROUPBY"] = old
    _assert_streams_equal(got, want)


# ---------------------------------------------------------------------------
# public API end-to-end


class _NumSchema(pw.Schema):
    a: int
    b: float


def _public_rows(n=200, seed=23):
    rng = np.random.default_rng(seed)
    return [
        (int(rng.integers(-500, 500)), float(rng.normal()))
        for _ in range(n)
    ]


def _public_build_and_collect():
    pw.internals.parse_graph.G.clear()
    t = pw.debug.table_from_rows(_NumSchema, _public_rows())
    r = t.select(x=t.a * 2 + 1, y=t.b - t.a).filter(
        pw.this.x > 0
    ).select(z=pw.this.x + 1, y=pw.this.y)
    keys, cols = pw.debug.table_to_dicts(r)
    rt = pw.internals.parse_graph.G.last_runtime
    return keys, cols, rt


def test_public_api_differential():
    os.environ["PATHWAY_COMPILED_TICK"] = "0"
    os.environ["PATHWAY_COMPILED_MIN_ROWS"] = "1"
    try:
        _, cols_i, rt_i = _public_build_and_collect()
        assert rt_i.compiled_plan is None
        os.environ["PATHWAY_COMPILED_TICK"] = "1"
        _, cols_c, rt_c = _public_build_and_collect()
    finally:
        os.environ.pop("PATHWAY_COMPILED_TICK", None)
        os.environ.pop("PATHWAY_COMPILED_MIN_ROWS", None)
    assert rt_c.compiled_plan is not None
    assert sum(s.compiled_ticks for s in rt_c.compiled_plan.segments) > 0
    assert set(cols_i["z"]) == set(cols_c["z"])
    for k in cols_i["z"]:
        assert int(cols_i["z"][k]) == int(cols_c["z"][k])
        assert math.isclose(
            float(cols_i["y"][k]), float(cols_c["y"][k]), rel_tol=1e-9
        )


def test_debug_graph_reports_segments():
    os.environ["PATHWAY_COMPILED_TICK"] = "1"
    os.environ["PATHWAY_COMPILED_MIN_ROWS"] = "1"
    try:
        _, _, rt = _public_build_and_collect()
    finally:
        os.environ.pop("PATHWAY_COMPILED_TICK", None)
        os.environ.pop("PATHWAY_COMPILED_MIN_ROWS", None)
    from pathway_tpu.observability.debug import graph_table

    rows = graph_table(rt)
    tails = [r for r in rows if r.get("segment_tail")]
    assert tails, "no segment tail rows in /debug/graph"
    assert any(r["compiled_ticks"] > 0 for r in tails)
    assert all("compiled" in r for r in rows)


# ---------------------------------------------------------------------------
# Graph Doctor: compile-boundary rule


def test_doctor_compile_boundary_names_udf():
    from pathway_tpu.analysis import run_doctor

    pw.internals.parse_graph.G.clear()
    t = pw.debug.table_from_rows(_NumSchema, [(1, 1.0), (2, 2.0)])
    m = t.select(x=t.a * 2)
    u = m.select(
        u=pw.apply(lambda x: x + 1, pw.this.x), x=pw.this.x
    )
    pw.io.null.write(u.select(y=pw.this.u + pw.this.x))
    report = run_doctor()
    diags = report.by_rule("compile-boundary")
    assert diags, "expected a compile-boundary diagnostic for the UDF"
    assert any("UDF" in d.message or "udf" in d.message for d in diags)


def test_doctor_compile_boundary_negative_pure_chain():
    from pathway_tpu.analysis import run_doctor

    pw.internals.parse_graph.G.clear()
    t = pw.debug.table_from_rows(_NumSchema, [(1, 1.0), (2, 2.0)])
    pw.io.null.write(t.select(x=t.a * 2).filter(pw.this.x > 0))
    report = run_doctor()
    assert not report.by_rule("compile-boundary")
