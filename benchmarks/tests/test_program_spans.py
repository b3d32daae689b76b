"""The readers of the program's own spans, on a made-up context, and a traced
rehearsal of both cells whose line carries what they read."""

import json
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.harness import program_spans
from benchmarks.harness import trace as tracing
from benchmarks.harness.loader import load_cell, read_benchmark
from benchmarks.harness.runtime import ReduceContext
from benchmarks.harness.spans import Spans
from benchmarks.reducers import program_span_idle, program_span_ms, program_span_ratio

CELLS = [w["name"] for w in read_benchmark()["workloads"]]
TO_TRACE = -90.0  # the made-up profiler's clock reads 90 s less than perf_counter


def record(name, t0, t1, span_id, parent_id=None, **attributes):
    return SimpleNamespace(
        name=name, start_perf_ns=round(t0 * 1e9), duration_ns=round((t1 - t0) * 1e9),
        span_id=span_id, parent_id=parent_id, attributes=attributes,
    )


def program_records():
    """Two traced ticks as the program records them (a child before its
    parent: a span is recorded when it ends), a warm-up span before the
    window and a tick after the traced part."""
    return [
        record("embed.batch", 50.0, 51.0, "warm"),
        record("embed.tokenize", 100.0, 100.1, "t0", "b0", tokens_real=10, len_bucket=16),
        record("embed.forward", 100.1, 100.4, "f0", "b0", tokens_real=10, tokens_padded=128),
        record("embed.batch", 100.0, 100.4, "b0"),
        record("corpus.upload", 100.45, 100.55, "u0", "s0", bytes=1000),
        record("corpus.prepare", 100.55, 100.6, "p0", "s0"),
        record("index.topk", 100.6, 100.95, "k0", "s0"),
        record("index.search", 100.4, 101.0, "s0"),
        record("embed.tokenize", 101.0, 101.05, "t1", "b1", tokens_real=30, len_bucket=16),
        record("embed.forward", 101.05, 101.3, "f1", "b1", tokens_real=30, tokens_padded=128),
        record("embed.batch", 101.0, 101.3, "b1"),
        record("corpus.upload", 101.3, 101.5, "u1", "s1", bytes=3000),
        record("corpus.prepare", 101.5, 101.6, "p1", "s1"),
        record("index.topk", 101.6, 101.9, "k1", "s1"),
        record("index.search", 101.3, 102.0, "s1"),
        record("embed.batch", 110.0, 111.0, "late"),
    ]


HARNESS = [
    ("embed", 0, 100.0, 100.4), ("search", 0, 100.4, 101.0),
    ("embed", 1, 101.0, 101.3), ("search", 1, 101.3, 102.0),
    ("embed", 2, 110.0, 111.0),  # a tick after the traced part: not read
]
DEVICE_OPS = [
    (9.92, 9.94, "stray"),  # before the first tick: idle around it belongs to no span
    (10.15, 10.35, "forward"), (10.56, 10.59, "prepare"), (10.65, 10.9, "topk"),
    (11.12, 11.25, "forward"), (11.52, 11.58, "prepare"), (11.65, 11.85, "topk"),
]


def context(device=True, peaks=None, jitter=(0.0, 2e-6, -2e-6, 1e-6)) -> ReduceContext:
    spans = Spans()
    spans.records = list(HARNESS)
    seen = [
        (t0 + TO_TRACE + jitter[i], t1 + TO_TRACE, "bench." + name)
        for i, (name, _tick, t0, t1) in enumerate(HARNESS[:4])
    ]
    trace = tracing.Trace(
        device_ops={"/device:TPU:0": list(DEVICE_OPS)} if device else {},
        spans=sorted(seen + [(9.9, 12.1, "bench.window")]),
    )
    return ReduceContext(spans, [{}, {}], 2.2, trace, peaks, 1, {})


@pytest.fixture
def program(monkeypatch):
    """What the readers are given as the program's ring; a test may change it."""
    ring = {"records": program_records(), "dropped": 0}
    monkeypatch.setattr(program_spans, "tracer_records", lambda: (ring["records"], ring["dropped"]))
    return ring


def test_each_span_gets_the_tick_of_the_harness_span_that_holds_it(program):
    spans = program_spans.read(context())
    assert [s.span_id for s in spans] == [
        "b0", "t0", "f0", "s0", "u0", "p0", "k0", "b1", "t1", "f1", "s1", "u1", "p1", "k1",
    ]  # by start, a parent before its children; warm-up and the late tick are gone
    assert [s.tick for s in spans] == [0] * 7 + [1] * 7
    own = program_spans.self_seconds(spans)
    assert own["s0"] == pytest.approx(0.6 - 0.1 - 0.05 - 0.35)
    assert own["t0"] == pytest.approx(0.1)  # no children


def test_ms_readers(program):
    ctx = context()
    value, extra = program_span_ms.reduce(ctx, ["embed.tokenize"])
    assert value == pytest.approx(75.0) and extra == {}
    value, extra = program_span_ms.reduce(ctx, ["embed.forward"], device_ms=True)
    assert value == pytest.approx(275.0) and extra["device_ms"] == pytest.approx(165.0)
    value, extra = program_span_ms.reduce(ctx, ["index.search"], self_time=True)
    assert value == pytest.approx(100.0)
    value, extra = program_span_ms.reduce(
        ctx, ["corpus.upload"], per_tick_sums={"bytes_per_tick": "bytes"}
    )
    assert value == pytest.approx(150.0) and extra == {"bytes_per_tick": 2000}
    value, extra = program_span_ms.reduce(ctx, ["corpus.prepare"], device_ms=True)
    assert value == pytest.approx(75.0) and extra["device_ms"] == pytest.approx(45.0)
    # spans of several names add up within a tick
    value, _ = program_span_ms.reduce(ctx, ["embed.tokenize", "embed.forward"])
    assert value == pytest.approx(350.0)


def test_useful_share_of_the_padded_tokens(program):
    value, extra = program_span_ratio.reduce(
        context(peaks={"flops_per_s": 1.0}), "embed.forward", "tokens_real", "tokens_padded"
    )
    assert value == pytest.approx(100.0 * 40 / 256)
    assert extra == {"tokens_real": 40, "tokens_padded": 256}


def test_idle_time_goes_to_the_innermost_span(program):
    ctx = context()
    value, extra = program_span_idle.reduce(ctx, "index.search")
    by_span = extra["idle_s_by_span"]
    # each gap is cut where the spans change: (9.94, 10.15) gives 0.06 s to no
    # span, 0.1 to the tokenizer and 0.05 to the forward that had begun
    assert by_span == {
        "(none)": pytest.approx(0.02 + 0.06 + 0.1),
        "embed.tokenize": pytest.approx(0.1 + 0.05),
        "embed.forward": pytest.approx(0.05 + 0.05 + 0.07 + 0.05),
        "index.search": pytest.approx(0.05 + 0.05 + 0.1),  # its self time only
        "corpus.upload": pytest.approx(0.1 + 0.2),
        "corpus.prepare": pytest.approx(0.01 + 0.01 + 0.02 + 0.02),
        "index.topk": pytest.approx(0.05 + 0.05 + 0.05 + 0.05),
    }
    busy = sum(b - a for a, b, _ in DEVICE_OPS)
    assert sum(by_span.values()) == pytest.approx(2.2 - busy)  # every idle second has a name
    assert value == pytest.approx(100 * (0.2 + 0.3 + 0.06 + 0.2) / 2.2)  # children included
    value, _ = program_span_idle.reduce(ctx, "corpus.upload")
    assert value == pytest.approx(100 * 0.3 / 2.2)
    per_tick = extra["tick_ms_by_span"]
    assert per_tick["embed.batch"] == pytest.approx(350.0)
    assert per_tick["embed.batch (self)"] == pytest.approx(0.0, abs=1e-6)
    assert per_tick["index.search"] == pytest.approx(650.0)
    assert per_tick["index.search (self)"] == pytest.approx(100.0)
    parts = ["corpus.upload", "corpus.prepare", "index.topk", "index.search (self)"]
    assert sum(per_tick[p] for p in parts) == pytest.approx(per_tick["index.search"])


def test_the_offset_is_the_median_over_the_paired_harness_spans(program):
    offset, spread = program_spans.clock_offset(context())
    assert offset == pytest.approx(TO_TRACE + 0.5e-6, abs=1e-9) and 0 < spread < 5e-6
    # one span in forty whose thread lost the core between stamp and read: no drift
    differences = [TO_TRACE + 1e-6 * (i % 3) for i in range(40)]
    differences[17] += 5e-3
    assert program_spans.spread_of(differences) < 1e-5
    assert program_spans.spread_of([TO_TRACE]) == 0.0


def test_refused_a_tick_without_a_named_span(program):
    for r in program["records"]:
        if r.span_id == "t1":
            r.name = "embed.tokenise"  # renamed in the program
    with pytest.raises(RuntimeError, match=r"tick 1 has no program span 'embed.tokenize'"):
        program_span_ms.reduce(context(), ["embed.tokenize"])
    with pytest.raises(RuntimeError, match="'corpus.uploaded'"):
        program_span_idle.reduce(context(), "corpus.uploaded")


def test_refused_a_ring_that_lost_part_of_the_window(program):
    program["dropped"] = 5
    assert program_spans.read(context()) is not None  # the oldest is older than the window
    program["records"] = program["records"][2:]  # ... and now younger than its start
    with pytest.raises(RuntimeError, match="overwrote 5 records"):
        program_span_ms.reduce(context(), ["embed.forward"])


def test_refused_harness_spans_that_disagree_on_the_offset(program):
    drifting = context(jitter=(0.0, 0.0, 0.5e-3, 0.5e-3))
    with pytest.raises(RuntimeError, match="disagree on the offset"):
        program_span_ms.reduce(drifting, ["embed.forward"], device_ms=True)
    assert program_span_ms.reduce(drifting, ["embed.forward"])[0] == pytest.approx(275.0)
    unpaired = context()
    unpaired.trace.spans.pop()
    with pytest.raises(RuntimeError, match="the trace holds 3 harness spans"):
        program_spans.clock_offset(unpaired)


def test_nothing_to_read_returns_nothing(program):
    # a program from before these spans: records without start_perf_ns
    program["records"] = [SimpleNamespace(name="embed.batch", start_unix_ns=1, duration_ns=1)]
    ctx = context(peaks={"flops_per_s": 1.0})
    assert program_span_ms.reduce(ctx, ["embed.tokenize"], device_ms=True) is None
    assert program_span_ratio.reduce(ctx, "embed.forward", "tokens_real", "tokens_padded") is None
    assert program_span_idle.reduce(ctx, "index.search") is None
    program["records"] = []  # tracing switched off
    assert program_span_ms.reduce(ctx, ["embed.tokenize"]) is None
    # a rehearsal: no device plane, no peaks; the host times are still read
    program["records"] = program_records()
    toy = context(device=False)
    assert program_span_idle.reduce(toy, "index.search") is None
    assert program_span_ratio.reduce(toy, "embed.forward", "tokens_real", "tokens_padded") is None
    assert program_span_ms.reduce(toy, ["embed.forward"], device_ms=True) == (
        pytest.approx(275.0), {},
    )


def rehearse(cell, capsys):
    argv = ["--workload", cell, "--seed", "2147483659", "--seconds", "1", "--trace", "1", "--rehearse"]
    run.main(argv)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reads_the_programs_spans(cell, capsys):
    metrics = rehearse(cell, capsys)["metrics"]
    kind = cell.rsplit(".", 1)[1]
    from_program = [
        m["name"] for m in load_cell(cell).per_layer
        if m["source"] == "program_span" and m["unit"] == "ms"
    ]
    assert len(from_program) >= 3 and set(from_program) <= set(metrics)
    assert all(metrics[name]["value"] > 0 for name in from_program)
    # the inside of a call is less than the harness's span around the call
    inside = metrics[f"tokenize_ms.{kind}"]["value"] + metrics[f"forward_ms.{kind}"]["value"]
    assert inside < metrics[f"embed_ms.{kind}"]["value"]
    if kind == "ingest":
        assert metrics["corpus_upload_ms.ingest"]["bytes_per_tick"] > 0
        refresh = metrics["corpus_upload_ms.ingest"]["value"] + metrics["corpus_prepare_ms.ingest"]["value"]
        assert refresh < metrics["index_refresh_ms.ingest"]["value"]
    else:
        assert metrics["search_host_ms.retrieve"]["value"] < metrics["search_ms.retrieve"]["value"]


def test_a_renamed_program_span_fails_the_traced_run(capsys, monkeypatch):
    from pathway_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    sound = tracer.span
    renamed = lambda name, **kw: sound("embed.tok" if name == "embed.tokenize" else name, **kw)  # noqa: E731
    monkeypatch.setattr(tracer, "span", renamed)
    with pytest.raises(RuntimeError, match="no program span 'embed.tokenize'"):
        rehearse(CELLS[0], capsys)
