"""``reducers/scope_device_ms.py`` on a hand-made trace and table: device
time by the program's scopes, each instant charged once."""

import sys

import pytest

from benchmarks.harness import trace as tracing
from benchmarks.harness.runtime import ReduceContext
from benchmarks.harness.spans import Spans
from benchmarks.reducers import scope_device_ms
from pathway_tpu.observability import device_scopes
from pathway_tpu.observability.device_scopes import Row

GATHER = "%fusion.7 = bf16[512,64]{1,0:T(8,128)(2,1)} fusion(bf16[64,64]{1,0:T(8,128)(2,1)} %p.1, s32[512]{0} %src), kind=kLoop"
KERNEL = "%moe_grouped_matmul.3 = bf16[512,32]{1,0:T(8,128)(2,1)} custom-call(s32[4]{0} %tiles, bf16[512,64]{1,0} %fusion.7)"
COMBINE = "%fusion.9 = f32[128,64]{1,0:T(8,128)} fusion(bf16[512,64]{1,0} %y, s32[128,4]{1,0} %dest), kind=kLoop"
WHILE = "%while.1 = (s32[]{:T(128)}, f32[128,64]{1,0:T(8,128)}) while((s32[]{:T(128)}, f32[128,64]{1,0}) %tuple.4), condition=%cond, body=%body"  # its computations are no operands
SCORES = "%fusion.2 = (f32[2,2048]{1,0:T(2,128)}, f32[2,16]{1,0}) fusion(f32[2,16]{1,0} %q, f32[2048,16]{1,0} %prep), kind=kOutput"
COPY = "%copy.5 = f32[128,64]{0,1:T(8,128)} copy(f32[128,64]{1,0:T(8,128)} %fusion.9)"
STRANGER = "%fusion.99 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"

TABLES = {
    "jit_forward ids[8, 32]": [
        Row("fusion.7", "bf16[512,64]", "fusion", "trunk.moe.gather", operands=("p.1", "src")),
        Row("moe_grouped_matmul.3", "bf16[512,32]", "custom-call", "trunk.moe.experts", operands=("tiles", "fusion.7")),
        Row("fusion.9", "f32[128,64]", "fusion", "trunk.moe.combine", 2, ("y", "dest")),
        Row("while.1", "(s32[], f32[128,64])", "while", "trunk.moe", operands=("tuple.4",)),
        Row("copy.5", "f32[128,64]", "copy", device_scopes.NO_SCOPE, operands=("fusion.9",)),
    ],
    "jit_dense_topk_prepared queries[2, 16]": [
        Row("fusion.2", "(f32[2,2048], f32[2,16])", "fusion", "knn.scores", operands=("q", "prep")),
    ],
}
# two ticks; the while of the second lies around its body's operations, and
# the window cuts the last operation in half
DEVICE_OPS = [
    (9.0, 9.5, SCORES),  # before the window
    (10.0, 10.2, GATHER), (10.2, 10.5, KERNEL), (10.5, 10.6, COMBINE), (10.6, 10.65, COPY),
    (10.7, 10.8, SCORES),
    (11.0, 11.7, WHILE), (11.1, 11.3, GATHER), (11.3, 11.5, KERNEL), (11.5, 11.6, COMBINE),
    (11.9, 12.1, SCORES),
]
WINDOW = (9.9, 12.0)


def context(ops=DEVICE_OPS, ticks=2) -> ReduceContext:
    trace = tracing.Trace(
        device_ops={"/device:TPU:0": list(ops)} if ops else {},
        spans=[(WINDOW[0], WINDOW[1], "bench.window")],
    )
    return ReduceContext(Spans(), [{}] * ticks, WINDOW[1] - WINDOW[0], trace, None, 1, {})


class Given(dict):
    """The program's tables as a test gives them, and how often they were asked for."""

    calls = 0


@pytest.fixture
def tables(monkeypatch):
    given = Given({name: list(rows) for name, rows in TABLES.items()})

    def asked():
        given.calls += 1
        return given

    monkeypatch.setattr(device_scopes, "tables", asked)
    return given


def read(scopes, **kw):
    return scope_device_ms.reduce(context(**kw), scopes)


def test_innermost_event_takes_each_instant_once():
    seconds = scope_device_ms.innermost_seconds(
        [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (3.0, 4.0, "b"), (6.0, 12.0, "late"), (20.0, 21.0, "alone")]
    )
    # "late" starts inside the while and outlives it: the innermost from its start on
    assert seconds == pytest.approx({"while": 3.0, "a": 2.0, "b": 1.0, "late": 6.0, "alone": 1.0})
    assert sum(seconds.values()) == pytest.approx(13.0)  # the union: nothing twice, nothing lost


def test_the_split_adds_up_to_the_busy_union(tables):
    around = ["trunk.moe.route", "trunk.moe.dispatch", "trunk.moe.gather", "trunk.moe.combine"]
    value, extra = read(around)
    # gather 0.2 + 0.2, combine 0.1 + 0.1, over two ticks
    assert value == pytest.approx(1e3 * 0.6 / 2)
    by_scope = extra["device_ms_by_scope"]
    assert set(by_scope) == set(device_scopes.VOCABULARY) | {
        device_scopes.NO_SCOPE, scope_device_ms.AMBIGUOUS, scope_device_ms.UNMATCHED,
    }
    busy = tracing.mean_busy(context().trace, [WINDOW])
    assert busy == pytest.approx(1.55)
    assert sum(by_scope.values()) * 2 == pytest.approx(1e3 * busy)
    assert by_scope["trunk.moe.experts"] == pytest.approx(1e3 * 0.5 / 2)
    assert by_scope["knn.scores"] == pytest.approx(1e3 * 0.2 / 2)  # 0.1, and 0.1 of the op the window cuts
    assert by_scope[device_scopes.NO_SCOPE] == pytest.approx(1e3 * 0.05 / 2)
    assert by_scope["trunk.mla"] == 0.0  # a scope without device time reads 0.0
    assert extra["matched_pct"] == pytest.approx(100.0) and extra["ambiguous_pct"] == 0.0
    assert extra["mixed_pct"] == pytest.approx(100 * 0.2 / 1.55)  # the combine's fusion spans two scopes
    assert extra["no_scope_ops"] == [[COPY[:64], pytest.approx(0.05)]]
    assert extra["programs"] == 2 and extra["tables_s"] >= 0.0


def test_a_while_around_its_body_is_charged_once(tables):
    _value, extra = read(["trunk.moe"])
    # the second tick's while spans 0.7 s, 0.5 of them its body's operations
    assert extra["device_ms_by_scope"]["trunk.moe"] == pytest.approx(1e3 * 0.2 / 2)
    assert extra["device_ms_by_scope"]["trunk.moe.gather"] == pytest.approx(1e3 * 0.4 / 2)


def test_a_cells_metrics_share_one_reading_of_the_tables(tables):
    one = context()
    scope_device_ms.reduce(one, ["knn.scores"])
    scope_device_ms.reduce(one, ["knn.topk"])
    assert tables.calls == 1
    scope_device_ms.reduce(context(), ["knn.topk"])  # another trace: read again
    assert tables.calls == 2


def test_an_ambiguous_key_is_reported_and_not_guessed(tables):
    tables["jit_forward ids[16, 32]"] = [Row("fusion.2", "(f32[2,2048], f32[2,16])", "fusion", "trunk.mhc", operands=("q", "prep"))]
    ops = DEVICE_OPS + [(11.70, 11.71, SCORES)]  # 1% more: still over MIN_MATCHED without it
    with pytest.raises(RuntimeError, match="joins the program's 3 tables"):
        read(["knn.scores"], ops=ops)
    # another program's instruction of that name and type reads other operands: no doubt
    tables["jit_forward ids[16, 32]"] = [Row("fusion.2", "(f32[2,2048], f32[2,16])", "fusion", "trunk.mhc", operands=("x",))]
    assert read(["knn.scores"], ops=ops)[1]["ambiguous_pct"] == 0.0
    # nor is the same name, type and operands under the same scope in two programs
    tables["jit_forward ids[16, 32]"] = [Row("fusion.2", "(f32[2,2048], f32[2,16])", "fusion", "knn.scores", operands=("q", "prep"))]
    value, extra = read(["knn.scores"], ops=ops)
    assert extra["ambiguous_pct"] == 0.0 and value == pytest.approx(1e3 * 0.21 / 2)
    # a little of it is reported beside the split, charged to no scope
    tables["jit_forward ids[16, 32]"] = [Row("fusion.99", "f32[8]", "fusion", "trunk.mhc", operands=("x",))]
    tables["jit_forward ids[32, 32]"] = [Row("fusion.99", "f32[8]", "fusion", "trunk.mla", operands=("x",))]
    value, extra = read(["trunk.mhc"], ops=DEVICE_OPS + [(11.70, 11.71, STRANGER)])
    assert value == 0.0
    assert extra["ambiguous_pct"] == pytest.approx(100 * 0.01 / 1.56)
    assert extra["matched_pct"] == pytest.approx(100 - 100 * 0.01 / 1.56)
    assert extra["device_ms_by_scope"][scope_device_ms.AMBIGUOUS] == pytest.approx(1e3 * 0.01 / 2)
    assert extra["no_scope_ops"][1] == [STRANGER[:64], pytest.approx(0.01)]


def test_an_operation_of_no_table_fails_the_reading_once_it_weighs(tables):
    _value, extra = read(["knn.scores"], ops=DEVICE_OPS + [(11.70, 11.72, STRANGER)])
    assert extra["matched_pct"] == pytest.approx(100 - 100 * 0.02 / 1.57)
    assert extra["device_ms_by_scope"][scope_device_ms.UNMATCHED] == pytest.approx(1e3 * 0.02 / 2)
    with pytest.raises(RuntimeError, match=r"fusion\.99"):
        read(["knn.scores"], ops=DEVICE_OPS + [(11.70, 11.80, STRANGER)])


def test_a_scope_outside_the_vocabulary_raises(tables):
    with pytest.raises(ValueError, match="trunk.moe.renamed"):
        read(["trunk.moe.gather", "trunk.moe.renamed"])


def test_a_program_without_device_scopes_reads_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "pathway_tpu.observability.device_scopes", None)
    import pathway_tpu.observability as package

    monkeypatch.delattr(package, "device_scopes", raising=False)
    assert scope_device_ms.reduce(context(), ["knn.scores"]) is None


def test_a_rehearsal_has_no_device_plane_to_read(tables):
    assert read(["knn.scores"], ops=None) is None
    assert tables.calls == 0


def test_every_scope_metric_names_scopes_of_the_vocabulary():
    from benchmarks.harness.loader import load_metric_reader, read_benchmark

    readers = {}
    for metric in read_benchmark()["per_layer"]:
        reduce, args = load_metric_reader(metric["name"])
        if reduce is scope_device_ms.reduce:
            assert metric["source"] == "device_trace" and metric["unit"] == "ms"
            assert set(args["scopes"]) <= set(device_scopes.VOCABULARY)
            readers[metric["name"]] = args["scopes"]
    assert len(readers) == 8
