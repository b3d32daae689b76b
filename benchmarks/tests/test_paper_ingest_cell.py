"""The paper cell's own pieces: the rehearsal runs and reads every new
metric's span and counter, the fp8 control and the no-carry control are not
``correct`` on three seeds, a timed path broken underneath (a scan that
forgets its state, a sigmoid router) likewise, the work counts are the
issue's arithmetic, a program without the kind is refused at once, and every
seed is dealt the same work: whole passes and the same probed rungs."""

import json
import types

import numpy as np
import pytest

from benchmarks import run, study_controls
from benchmarks.drivers import paper_ingest_ticks
from benchmarks.harness import work_ssm
from benchmarks.harness.loader import load_cell
from benchmarks.harness.traffic import TickStream, word_count
from benchmarks.reducers import op_roofline_ssm, window_mfu_ssm

CELL = "granite-4.0-h-small.paper-ingest"


def rehearse(capsys, trace=0, seed=3000000019):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace), "--rehearse"]
    run.main(argv)
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def over(last) -> set:
    return {name for name, c in last["compared"].items() if c["value"] > c["limit"]}


def test_a_traced_rehearsal_reads_the_programs_spans_and_counters(capsys):
    last, _err = rehearse(capsys, trace=1, seed=2147483659)
    metrics = last["metrics"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["window"]["ticks"] % 8 == 0 and last["attempted"] == 4 * last["window"]["ticks"]  # whole passes
    for name in ("tokenize_ms", "forward_ms", "embed_ms", "index_refresh_ms", "corpus_upload_ms", "corpus_prepare_ms"):
        assert metrics[f"{name}.paper-ingest"]["value"] > 0
    inside = metrics["tokenize_ms.paper-ingest"]["value"] + metrics["forward_ms.paper-ingest"]["value"]
    assert inside < metrics["embed_ms.paper-ingest"]["value"]
    # the shares of a peak and the counters' ratios are a chip's to report
    for name in (
        "step_mfu.paper-ingest", "ssd_scan_roofline", "moe_experts_roofline.paper-ingest",
        "ssm_chunks_useful_pct.paper-ingest", "attn_pairs_useful_pct.paper-ingest",
    ):
        assert name not in metrics
    from pathway_tpu.observability.tracing import get_tracer

    forwards = [r for r in get_tracer().spans() if r.name == "embed.forward" and r.attributes.get("trunk") == "granite-4.0-h-small"]
    assert forwards
    for r in forwards:
        a = r.attributes
        assert a["ssm_chunks_visited"] >= a["ssm_chunks_useful"] > 0 and a["ssm_chunks_useful"] % 9 == 0
        assert a["attn_pairs_visited"] >= a["attn_pairs_allowed"] > 0
        assert a["expert_rows_computed"] >= a["expert_rows_useful"] > 0 and a["tokens_padded"] >= a["tokens_real"]


@pytest.mark.parametrize("seeds", ["11,2147483659,3000000019"])
def test_both_controls_are_not_correct_on_three_seeds(capsys, seeds):
    lines = study_controls.main(
        ["--workload", CELL, "--seeds", seeds, "--controls", "fp8,no_carry", "--seconds", "0.5", "--rehearse"]
    )
    capsys.readouterr()
    assert len(lines) == 3 and study_controls.verdict(lines, ["fp8", "no_carry"]) == 0
    for line in lines:
        assert not line["program_over"] and line["program"]["replay_err"] == 0
        assert {"vec_err", "route_gap", "topk_gap", "score_err"} <= set(line["fp8_over"])
        # the state's control is arithmetic in float32: only the encoder's numbers can see it
        assert {"vec_err", "route_gap"} <= set(line["no_carry_over"])
        assert line["no_carry"]["vec_err"] > 4 * line["program"]["vec_err"]


def test_a_scan_that_forgets_the_carried_state_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: every chunk starts from a zero state."""
    import jax.numpy as jnp

    from pathway_tpu.ops import ssd_scan

    sound = ssd_scan.scan

    def forgetful(x, dt, A, B, C, D, chunk=ssd_scan.CHUNK):
        parts = [(x[:, i : i + chunk], dt[:, i : i + chunk], B[:, i : i + chunk], C[:, i : i + chunk]) for i in range(0, x.shape[1], chunk)]
        return jnp.concatenate([sound(xc, dtc, A, bc, cc, D, chunk) for xc, dtc, bc, cc in parts], axis=1)

    monkeypatch.setattr(ssd_scan, "scan", forgetful)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)
    assert last["compared"]["replay_err"]["value"] == 0 and last["compared"]["stale_probes"]["value"] == 0


def test_a_router_that_weighs_by_sigmoid_scores_is_not_correct(capsys, monkeypatch):
    from pathway_tpu.xpacks.llm import _trunk

    sound = _trunk.TrunkConfig.from_dict

    def sigmoid(config, **overrides):
        return sound(dict(config, scoring_func="sigmoid"), **overrides)

    monkeypatch.setattr(_trunk.TrunkConfig, "from_dict", sigmoid)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)


def test_a_program_without_the_kind_is_refused_at_once(monkeypatch):
    from benchmarks.harness import sut_ssm
    from pathway_tpu.xpacks.llm import _trunk

    cell = load_cell(CELL, rehearse=True)
    monkeypatch.setattr(_trunk, "ATTENTION", {k: v for k, v in _trunk.ATTENTION.items() if k != "mamba2"})
    with pytest.raises(SystemExit, match="mamba2"):
        sut_ssm.build_embedder(cell.config, cell.config_name)


def test_the_cell_is_the_issues():
    cell = load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "paper_ingest_ticks"
    doc = load_cell("command-a-plus-05-2026.doc-ingest").traffic
    for key in ("ticks", "passes", "tick_size", "words", "vocabulary", "shape_seed", "check_ticks", "trace_seconds"):
        assert cell.traffic[key] == doc[key]  # the same 64 documents and the same plan
    assert cell.traffic["shape_seed"] == 20261002 and cell.traffic["words"]["mean"] == 4938
    names = {m["name"] for m in cell.per_layer}
    assert {"ssd_scan_roofline", "step_mfu.paper-ingest", "ssm_chunks_useful_pct.paper-ingest", "device_idle_pct.paper-ingest"} <= names
    assert len(names) == 15 and [m["name"] for m in cell.end_to_end] == ["ingest_docs_per_s", "setup_s"]
    assert cell.config["num_hidden_layers"] == 10 and cell.config["experts_held"] == [0, 36]
    assert cell.config["published"] == {"num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}


def dealt(traffic, seed: int, passes: int = 2) -> list[tuple]:
    """A run's ticks as work: each tick's rungs and its probe's, pass by pass."""
    rung = lambda text: 1 << word_count(text).bit_length()  # the CLS rides in front
    ticks = TickStream(traffic, seed)
    draws = np.random.default_rng(np.random.SeedSequence([seed, 5])).integers(0, 2**31, size=2048)
    probes = paper_ingest_ticks.equal_probes(traffic, ticks.first_pass(), draws)
    assert ((0 <= probes) & (probes < 4)).all()
    n = ticks.per_pass
    return [
        sorted((tuple(sorted(map(rung, ticks[i]))), rung(ticks[i][probes[i]])) for i in range(p * n, (p + 1) * n))
        for p in range(passes)
    ]


@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019, 3000000426])
def test_every_seed_is_dealt_the_same_work(seed):
    traffic = load_cell(CELL).traffic
    first, second = dealt(traffic, seed)
    assert first == second == dealt(traffic, 0, passes=1)[0]  # the same batches and the same probes a pass
    probed = [probe for _tick, probe in first]
    assert sum(probed) == 118_784 and {r: probed.count(r) for r in set(probed)} == {16384: 2, 8192: 7, 4096: 7}
    # what the seed still draws: the order, the words, and which document of a rung is probed
    ticks, other = TickStream(traffic, seed), TickStream(traffic, seed + 1)
    assert [len(t) for t in ticks.first_pass()] == [4] * 16 and ticks[0] != other[0]
    draws = np.arange(2048)
    picks = paper_ingest_ticks.equal_probes(traffic, ticks.first_pass(), draws).reshape(-1, 16)
    assert (picks != picks[0]).any()  # a tick with several documents on its rung probes each in turn


@pytest.mark.parametrize("out_at,closes_at", [(1, 16), (13, 16), (16, 16), (17, 32)])
def test_the_window_closes_with_its_pass(out_at, closes_at):
    """``--seconds`` run out with tick ``out_at``: the window goes on to its pass's end."""
    inner = types.SimpleNamespace(ticks=[], seed=7)
    inner.tick_done = lambda work: inner.ticks.append(work) or len(inner.ticks) == out_at  # True once, as a clock is not
    seen = paper_ingest_ticks._WholePasses(inner, 16)
    assert seen.seed == 7
    done = [seen.tick_done({}) for _ in range(closes_at)]
    assert done == [False] * (closes_at - 1) + [True] and len(inner.ticks) == closes_at


def test_work_counts_are_the_issues_arithmetic():
    config = load_cell(CELL).config
    assert work_ssm.experts_a_token_here(config) == 5.0
    tokens = 256 * 1000  # whole chunks: 128.5 allowed pairs a token on average
    assert work_ssm.chunk_pairs(tokens, 256) == tokens * 128.5 and work_ssm.chunk_pairs(300, 256) == 32896 + 44 * 45 // 2
    scan = work_ssm.scan_flops(config, tokens) / tokens / 9
    assert scan == 128 * (4 * 64 * 128 + 2 * 64 * 128.5) + 2 * 128 * 128.5 == 6_332_544  # 6.33 M a token and layer
    mixer = work_ssm.mamba_layer_flops(config)
    assert mixer == 2 * 4096 * 16768 + 2 * 8192 * 4096 + 2 * 4 * 8448
    ffn = 2 * 4096 * 72 + 6 * 4096 * 1536 + 6 * 4096 * 768 * 5
    assert mixer + scan + ffn == 343_582_848  # 343.6 M a Mamba layer
    per_token = (work_ssm.forward_flops(config, tokens) - work_ssm.attention_flops(config, tokens)) / tokens
    assert per_token == 9 * 343_582_848 + (2 * 4096 * 128 * 80 + ffn) == 3_308_842_112  # 3.309 GFLOP a real token
    assert 9 * (mixer + scan) == pytest.approx(1.90e9, rel=2e-3) and 10 * ffn == pytest.approx(1.33e9, rel=3e-3)
    assert work_ssm.attention_flops(config, 16384) == 4 * 32 * 128 * 16384 * 16385 // 2
    assert work_ssm.scan_bytes(config, 10) == 9 * 10 * (2 * 2 * 8192 + 2 * 2 * 128 + 4 * 128)
    assert work_ssm.expert_matmul_bytes(config, 0) == 10 * 3 * 36 * 4096 * 768 * 2
    assert work_ssm.expert_matmul_flops(config, 100) == 6 * 4096 * 768 * 5 * 100 * 10


def test_share_readers_return_nothing_without_a_chip_or_a_match():
    config = load_cell(CELL).config
    ticks = [{"encoder_tokens": [5000, 9000, 5000]}]
    trace = types.SimpleNamespace(window=(0.0, 1.0), device_ops={"/device:TPU:0": [(0.1, 0.3, "fusion.1")]})
    rehearsal = types.SimpleNamespace(peaks=None, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert window_mfu_ssm.reduce(rehearsal) is None
    assert op_roofline_ssm.reduce(rehearsal, patterns=["fusion"], calls="ssd_scan") is None
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    chip = types.SimpleNamespace(peaks=peaks, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert op_roofline_ssm.reduce(chip, patterns=["ssd_chunk_scan"], calls="ssd_scan") is None
    share, extra = op_roofline_ssm.reduce(chip, patterns=["^fusion"], calls="ssd_scan")
    least = sum(  # sequence by sequence; a scan moves more bytes than its multiplies take time
        max(work_ssm.scan_flops(config, t) / 197e12, work_ssm.scan_bytes(config, t) / 819e9) for t in (5000, 9000, 5000)
    )
    assert extra["bound"] == "memory" and share == pytest.approx(100 * least / 0.2)
    share, extra = op_roofline_ssm.reduce(chip, patterns=["^fusion"], calls="moe_experts")
    least = sum(
        max(work_ssm.expert_matmul_flops(config, t) / 197e12, work_ssm.expert_matmul_bytes(config, t) / 819e9)
        for t in (14000, 5000)  # batch by batch: the tick's documents, then the probe
    )
    assert share == pytest.approx(100 * least / 0.2)
    assert 0 < window_mfu_ssm.reduce(chip) < 100
