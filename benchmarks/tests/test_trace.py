"""The trace reduction on a small synthetic set of intervals."""

import pytest

from benchmarks.harness import trace as tracing
from benchmarks.harness.runtime import ReduceContext
from benchmarks.harness.spans import Spans
from benchmarks.reducers import device_idle, span_median_ms, span_roofline, window_mfu

PEAKS = {"flops_per_s": 100.0, "bytes_per_s": 10.0}


def synthetic() -> tracing.Trace:
    # a 10 s window; two ticks, each an embed span then a search span
    return tracing.Trace(
        device_ops={
            "/device:TPU:0": [
                (1.0, 2.0, "fusion.1"),  # inside embed 0
                (1.5, 2.5, "fusion.2"),  # overlaps fusion.1, still embed 0
                (3.0, 4.0, "topk"),  # inside search 0
                (6.0, 6.5, "fusion.1"),  # inside embed 1
                (7.0, 9.0, "topk"),  # inside search 1
                (11.0, 12.0, "late"),  # after the window
            ]
        },
        spans=sorted(
            [
                (0.0, 10.0, "bench.window"),
                (0.5, 2.75, "bench.embed"),
                (2.75, 5.0, "bench.search"),
                (5.5, 6.75, "bench.embed"),
                (6.75, 9.5, "bench.search"),
            ]
        ),
    )


def test_union_and_clip():
    merged = tracing.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)])
    assert merged == [(1, 2.5), (3, 5)]
    assert tracing.total(merged) == 3.5
    assert tracing.clip(merged, [(0, 1.25), (2, 3.5), (4.5, 9)]) == [
        (1, 1.25), (2, 2.5), (3, 3.5), (4.5, 5),
    ]


def test_busy_inside_spans():
    trace = synthetic()
    assert tracing.mean_busy(trace) == pytest.approx(1.5 + 1 + 0.5 + 2 + 1)
    assert tracing.mean_busy(trace, [trace.window]) == pytest.approx(5.0)
    assert tracing.mean_busy(trace, tracing.spans_named(trace, ["search"])) == pytest.approx(3.0)
    assert tracing.mean_busy(trace, tracing.spans_named(trace, ["embed"])) == pytest.approx(2.0)


def test_breakdown():
    trace = synthetic()
    ops = dict(map(tuple, tracing.top_device_ops(trace, trace.window)))
    assert ops == {"topk": pytest.approx(3.0), "fusion.1": pytest.approx(1.5), "fusion.2": pytest.approx(1.0)}
    gaps = dict(map(tuple, tracing.idle_gaps(trace, trace.window)))
    # 0-1 embed, 2.5-3 search, 4-6 (mid 5.0: search 0 ends there), 6.5-7 embed/search edge, 9-10
    assert sum(gaps.values()) == pytest.approx(5.0)
    assert gaps["bench.embed"] >= 1.0


def context(trace, peaks=PEAKS) -> ReduceContext:
    spans = Spans()
    spans.records = [
        ("embed", 0, 0.5, 2.75), ("search", 0, 2.75, 5.0),
        ("embed", 1, 5.5, 6.75), ("search", 1, 6.75, 9.5),
        ("embed", 2, 20.0, 29.0),  # a tick after the traced part: not read
    ]
    ticks = [
        {"topk": [(1, 10, 1, 1)], "encoder_tokens": [1, 1]},
        {"topk": [(1, 20, 1, 1)], "encoder_tokens": [2]},
    ]
    config = {"hidden_size": 1, "num_hidden_layers": 1}
    return ReduceContext(spans, ticks, 10.0, trace, peaks, 1, config)


def test_readers_on_the_synthetic_trace():
    ctx = context(synthetic())
    assert span_median_ms.reduce(ctx, ["embed"]) == pytest.approx((2250 + 1250) / 2)
    assert device_idle.reduce(ctx) == pytest.approx(50.0)
    # bytes: 10*2+10+4+8 = 42 and 20*2+20+4+8 = 72 -> 11.4 s at 10 B/s; busy in search 3 s
    value, extra = span_roofline.reduce(ctx, ["search"], "topk")
    assert value == pytest.approx(100 * 11.4 / 3.0) and extra == {"bound": "memory"}
    # encoder 24*t + 4*t^2: 28, 28, 64; top-k 20 and 40 -> 180 FLOPs over 10 s at 100 FLOP/s
    assert window_mfu.reduce(ctx, ["encoder", "topk"]) == pytest.approx(18.0)


def test_readers_with_nothing_to_read_return_nothing():
    empty = tracing.Trace(spans=[(0.0, 1.0, "bench.window")])
    ctx = context(empty, peaks=None)
    assert device_idle.reduce(ctx) is None
    assert span_roofline.reduce(ctx, ["search"], "topk") is None
    assert window_mfu.reduce(ctx, ["encoder"]) is None


def test_clock_offset_puts_program_runs_back_inside_their_calls():
    calls = [(0.010 * i, 0.010 * i + 0.0095) for i in range(50)]  # back-to-back 9.5 ms calls
    # each call's program runs from 1 ms to 6 ms into it; the device stamps 1.8 ms early
    runs = [(a + 0.001 - 0.0018, a + 0.006 - 0.0018) for a, _ in calls]
    shift, share = tracing.clock_offset(runs, calls)
    assert share == 1.0
    # any shift from 0.8 to 5.3 ms puts every run inside its call: the middle is taken
    assert 0.0008 <= shift <= 0.0053
    assert shift == pytest.approx((0.0008 + 0.0053) / 2, abs=1e-4)
    assert tracing.clock_offset([], calls) == (0.0, 1.0)
    aligned = [(a + 0.001, a + 0.006) for a, _ in calls]
    assert abs(tracing.clock_offset(aligned, calls)[0] - 0.00125) < 1e-4  # feasible -1 .. 3.5 ms


def test_clock_offset_leaves_no_call_empty_where_it_can():
    # ticks of a long call then a short one (an ingest tick's embed and its probe).
    # Any shift from -10 to -0.7 ms puts each probe's program into the tail of the
    # long call before it: a wider stretch, and nearer to no shift, than the true
    # 0.8 to 3.8 ms, but one that leaves every probe call empty
    calls, runs = [], []
    for i in range(40):
        t = 0.200 * i
        calls += [(t, t + 0.120), (t + 0.1205, t + 0.1245)]
        runs += [(t + 0.012 - 0.0018, t + 0.100 - 0.0018), (t + 0.1215 - 0.0018, t + 0.1225 - 0.0018)]
    shift, share = tracing.clock_offset(runs, calls)
    assert share == 1.0 and 0.0008 <= shift <= 0.0038


def test_clock_offset_says_how_many_runs_it_could_not_place():
    calls = [(0.010 * i, 0.010 * i + 0.004) for i in range(50)]  # 4 ms calls, 6 ms apart
    inside = [(a + 0.001, a + 0.003) for a, _ in calls[:40]]
    pipelined = [(a + 0.003, a + 0.008) for a, _ in calls[40:]]  # outlast their calls
    _shift, share = tracing.clock_offset(inside + pipelined, calls)
    assert share == pytest.approx(0.8)
    assert share < tracing.MIN_CONTAINED  # read() refuses such a trace
