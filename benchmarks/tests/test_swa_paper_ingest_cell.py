"""The window-with-sinks cell's own pieces: the rehearsal runs and reads every
new metric's span and counter, the fp8 control and the no-sink control are
not ``correct`` on three seeds, a timed path whose kernel ignores the sink
likewise, a program without the kinds is refused at once, the work counts are
the configuration's arithmetic, and the share readers read nothing without a
chip or a match."""

import json
import types

import numpy as np
import pytest

from benchmarks import run, study_controls
from benchmarks.harness import traffic as traffic_mix
from benchmarks.harness import work_swa
from benchmarks.harness.loader import load_cell
from benchmarks.reducers import op_roofline_swa, window_mfu_swa

CELL = "mimo-v2.5.swa-paper-ingest"


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
        assert metrics[f"{name}.swa-paper-ingest"]["value"] > 0
    # the shares of a peak, the counters' ratios and the device's scopes are a chip's to report
    for name in (
        "step_mfu", "attn_block_roofline", "moe_experts_roofline", "window_pairs_useful_pct", "attn_pairs_useful_pct",
        "expert_rows_useful_pct", "useful_tokens_pct", "attn_around_kernel_ms", "moe_around_ms", "device_idle_pct",
    ):
        assert f"{name}.swa-paper-ingest" not in metrics
    from pathway_tpu.observability.tracing import get_tracer

    forwards = [r for r in get_tracer().spans() if r.name == "embed.forward" and r.attributes.get("trunk") == "mimo-v2.5"]
    assert forwards
    for r in forwards:
        a = r.attributes
        assert a["attn_window_pairs_visited"] >= a["attn_window_pairs_allowed"] > 0
        assert a["attn_pairs_visited"] > a["attn_window_pairs_visited"] and a["attn_pairs_allowed"] > a["attn_window_pairs_allowed"]
        assert a["expert_rows_computed"] >= a["expert_rows_useful"] > 0 and a["tokens_padded"] >= a["tokens_real"]
        assert "gdn_chunks_useful" not in a and "ssm_chunks_useful" not in a


@pytest.mark.parametrize("seeds", ["11,2147483659,3000000019"])
def test_both_controls_are_not_correct_on_three_seeds(capsys, seeds):
    lines = study_controls.main(
        ["--workload", CELL, "--seeds", seeds, "--controls", "fp8,no_sink", "--seconds", "0.5", "--rehearse"]
    )
    capsys.readouterr()
    assert len(lines) == 3 and study_controls.verdict(lines, ["fp8", "no_sink"]) == 0
    for line in lines:
        assert not line["program_over"] and line["program"]["replay_err"] == 0
        assert {"vec_err", "topk_gap", "score_err"} <= set(line["fp8_over"])
        # the sinks' control is arithmetic in float32: the encoder's numbers see it
        assert "vec_err" in line["no_sink_over"] and line["no_sink"]["vec_err"] > 10 * line["program"]["vec_err"]


def test_a_kernel_that_ignores_the_sink_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: the kernel called without its sinks."""
    from pathway_tpu.ops import block_attention

    sound = block_attention.attention

    def sinkless(q, k, v, *, sinks=None, **kw):
        return sound(q, k, v, **kw)

    monkeypatch.setattr(block_attention, "attention", sinkless)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)
    assert last["compared"]["replay_err"]["value"] == 0 and last["compared"]["stale_probes"]["value"] == 0


@pytest.mark.parametrize("kind", ["swa_sink", "gqa_partial"])
def test_a_program_without_a_kind_is_refused_at_once(monkeypatch, kind):
    from benchmarks.harness import sut_swa
    from pathway_tpu.xpacks.llm import _trunk

    cell = load_cell(CELL, rehearse=True)
    monkeypatch.setattr(_trunk, "ATTENTION", {k: v for k, v in _trunk.ATTENTION.items() if k != kind})
    with pytest.raises(SystemExit, match=kind):
        sut_swa.build_embedder(cell.config, cell.config_name)


def test_the_cell_reads_as_configured():
    cell = load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "swa_paper_ingest_ticks"
    paper = load_cell("qwen3-next-80b-a3b.gdn-paper-ingest").traffic
    for key in ("ticks", "passes", "tick_size", "words", "vocabulary", "shape_seed", "check_ticks"):
        assert cell.traffic[key] == paper[key]  # the same 64 documents and the same plan
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.swa-paper-ingest", "attn_block_roofline.swa-paper-ingest", "window_pairs_useful_pct.swa-paper-ingest"} <= names
    assert {"attn_around_kernel_ms.swa-paper-ingest", "moe_experts_roofline.swa-paper-ingest", "device_idle_pct.swa-paper-ingest"} <= names
    assert len(names) == 17 and [m["name"] for m in cell.end_to_end] == ["ingest_docs_per_s", "setup_s"]
    assert cell.config["num_hidden_layers"] == 7 and cell.config["experts_held"] == [0, 16]
    assert cell.config["published"] == {"num_hidden_layers": 48, "n_routed_experts": 256, "vocab_size": 152576}


def the_64_documents(traffic: dict) -> np.ndarray:
    """Real tokens (the words and the leading CLS) of the mix's one pass, as ``shape_seed`` draws them."""
    rng = np.random.default_rng(np.random.SeedSequence([int(traffic["shape_seed"]), 1]))
    sizes = traffic_mix._tick_sizes(traffic["tick_size"], int(traffic["ticks"]), rng)
    return np.concatenate([traffic_mix._word_counts(traffic["words"], int(b), rng) for b in sizes]) + 1


def test_work_counts_are_the_configurations_arithmetic():
    cell = load_cell(CELL)
    config = cell.config
    assert work_swa.experts_a_token_here(config) == 0.5
    assert work_swa.projection_flops(config, "window") == 188_743_680  # 188.7 M
    assert work_swa.projection_flops(config, "window") + work_swa.ffn_flops(config, True) == 216_006_656
    assert work_swa.projection_flops(config, "full") + work_swa.ffn_flops(config, True) == 205_520_896
    assert work_swa.projection_flops(config, "full") + work_swa.ffn_flops(config, False) == 580_911_104
    assert work_swa.token_flops(config) == 1_866_465_280  # 1,866.47 MFLOP a real token without pairs
    assert work_swa.pair_flops(config, "window") == work_swa.pair_flops(config, "full") == 40_960
    assert work_swa.attention_flops(config, 100) == 40_960 * 7 * 100 * 101 // 2  # shorter than the window
    tokens = the_64_documents(cell.traffic)
    assert (len(tokens), tokens.min(), tokens.max(), tokens.sum()) == (64, 1210, 11879, 321_252)
    per_token = sum(work_swa.forward_flops(config, int(t)) for t in tokens) / tokens.sum()
    assert per_token == pytest.approx(2_138.3e6, abs=0.05e6)  # 2,138.3 MFLOP a real token on these documents
    assert work_swa.attention_bytes(config, 1) == 5 * 2 * 23_040 + 2 * 2 * 21_760  # q, k, v, o at bfloat16
    assert work_swa.expert_matmul_bytes(config, 0) == 6 * 3 * 16 * 4096 * 2048 * 2
    assert work_swa.expert_matmul_flops(config, 100) == 6 * 4096 * 2048 * 0.5 * 100 * 6


def test_share_readers_return_nothing_without_a_chip_or_a_match():
    config = load_cell(CELL).config
    ticks = [{"encoder_tokens": [5000, 9000, 5000]}]
    trace = types.SimpleNamespace(window=(0.0, 1.0), device_ops={"/device:TPU:0": [(0.1, 0.3, "fusion.1")]})
    rehearsal = types.SimpleNamespace(peaks=None, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert window_mfu_swa.reduce(rehearsal) is None
    assert op_roofline_swa.reduce(rehearsal, patterns=["fusion"], calls="attn_block") is None
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    chip = types.SimpleNamespace(peaks=peaks, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert op_roofline_swa.reduce(chip, patterns=["^%?block_causal_attention"], calls="attn_block") is None
    share, extra = op_roofline_swa.reduce(chip, patterns=["^fusion"], calls="attn_block")
    least = sum(  # sequence by sequence; the allowed pairs take longer than their bytes
        max(work_swa.attention_flops(config, t) / 197e12, work_swa.attention_bytes(config, t) / 819e9)
        for t in (5000, 9000, 5000)
    )
    assert extra["bound"] == "compute" and share == pytest.approx(100 * least / 0.2)
    share, extra = op_roofline_swa.reduce(chip, patterns=["^fusion"], calls="moe_experts")
    least = sum(
        max(work_swa.expert_matmul_flops(config, t) / 197e12, work_swa.expert_matmul_bytes(config, t) / 819e9)
        for t in (14000, 5000)  # batch by batch: the tick's documents, then the probe
    )
    # about 440 rows a held expert in the tick's batch, 160 in the probe's: either side of the ridge
    assert extra["bound"] == "compute" and share == pytest.approx(100 * least / 0.2)
    tokens = [5000, 9000, 5000]
    assert window_mfu_swa.reduce(chip) == pytest.approx(100 * sum(work_swa.forward_flops(config, t) for t in tokens) / 197e12)
