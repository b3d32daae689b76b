"""The document cell's own pieces: the rehearsal runs and reads every new
metric's span and counter, the fp8 control and the no-window control are not
``correct`` on three seeds, a timed path broken underneath likewise, the
replay goes through the embedder's plan, the work counts are the issue's
arithmetic, and a program without the kinds is refused at once."""

import json
import types

import numpy as np
import pytest

from benchmarks import run, study_controls
from benchmarks.harness import work_gqa
from benchmarks.harness.loader import load_cell
from benchmarks.reducers import op_roofline_gqa, window_mfu_gqa

CELL = "command-a-plus-05-2026.doc-ingest"


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
    for name in ("tokenize_ms", "forward_ms", "embed_ms", "index_refresh_ms", "corpus_upload_ms", "corpus_prepare_ms"):
        assert metrics[f"{name}.doc-ingest"]["value"] > 0
    inside = metrics["tokenize_ms.doc-ingest"]["value"] + metrics["forward_ms.doc-ingest"]["value"]
    assert inside < metrics["embed_ms.doc-ingest"]["value"]
    # the shares of a peak and the counters' ratios are a chip's to report
    for name in ("step_mfu.doc-ingest", "attn_block_roofline", "moe_experts_roofline.doc-ingest", "attn_pairs_useful_pct.doc-ingest"):
        assert name not in metrics
    from pathway_tpu.observability.tracing import get_tracer

    forwards = [r for r in get_tracer().spans() if r.name == "embed.forward" and r.attributes.get("trunk") == "command-a-plus-05-2026"]
    assert forwards
    for r in forwards:
        a = r.attributes
        assert a["attn_pairs_visited"] >= a["attn_pairs_allowed"] > 0
        assert a["expert_rows_computed"] >= a["expert_rows_useful"] > 0 and a["tokens_padded"] >= a["tokens_real"]


@pytest.mark.parametrize("seeds", ["11,2147483659,3000000019"])
def test_both_controls_are_not_correct_on_three_seeds(capsys, seeds):
    lines = study_controls.main(
        ["--workload", CELL, "--seeds", seeds, "--controls", "fp8,no_window", "--seconds", "0.5", "--rehearse"]
    )
    capsys.readouterr()
    assert len(lines) == 3 and study_controls.verdict(lines, ["fp8", "no_window"]) == 0
    for line in lines:
        assert not line["program_over"] and line["program"]["replay_err"] == 0
        assert {"vec_err", "route_gap", "e2e_gap", "topk_gap", "score_err"} <= set(line["fp8_over"])
        # the window's control is arithmetic in float32: only the encoder's numbers can see it
        assert {"vec_err", "e2e_gap"} <= set(line["no_window_over"])
        assert line["no_window"]["vec_err"] > 5 * line["program"]["vec_err"]


def test_a_kernel_that_forgets_the_window_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: the blocked kernel is handed no
    window, so a window layer sees the whole row before it."""
    from pathway_tpu.ops import block_attention

    sound = block_attention.attention

    def forgetful(q, k, v, *, scale, window=None, **kw):
        return sound(q, k, v, scale=scale, window=None, **kw)

    monkeypatch.setattr(block_attention, "attention", forgetful)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)
    assert last["compared"]["replay_err"]["value"] == 0 and last["compared"]["stale_probes"]["value"] == 0


def test_shared_experts_summed_instead_of_averaged_are_not_correct(capsys, monkeypatch):
    from pathway_tpu.xpacks.llm import _trunk

    sound = _trunk.TrunkConfig.from_dict

    def summed(config, **overrides):
        return sound(dict(config, shared_expert_combination_strategy="sum"), **overrides)

    monkeypatch.setattr(_trunk.TrunkConfig, "from_dict", summed)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)


def test_a_program_without_the_kinds_is_refused_at_once(monkeypatch):
    from benchmarks.harness import sut_gqa
    from pathway_tpu.xpacks.llm import _trunk

    cell = load_cell(CELL, rehearse=True)
    monkeypatch.setattr(_trunk, "ATTENTION", {"mla": _trunk.ATTENTION["mla"]})
    with pytest.raises(SystemExit, match="gqa_window"):
        sut_gqa.build_embedder(cell.config, cell.config_name)


def test_the_replay_goes_through_the_plan():
    """A batch the embedder splits is replayed group by group: the vectors
    are the served ones exactly, and each text's choices stop at its rung."""
    import jax.numpy as jnp

    from benchmarks.harness import sut_gqa
    from pathway_tpu.xpacks.llm._trunk import TrunkRuntime

    cell = load_cell(CELL, rehearse=True)
    config = dict(cell.config, embedder={"max_len": 2048})
    embedder = sut_gqa.build_embedder(config, cell.config_name)
    embedder.runtime = TrunkRuntime(embedder.runtime.config, max_len=2048, seed=1, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    texts = [" ".join(rng.choice(["ab", "cd", "ef", "gh"], n - 1)) for n in (1900, 40, 700, 600, 90, 1500, 2000, 300, 50, 1100)]
    served = np.stack(embedder._embed_batch(texts))
    again, choices = sut_gqa.forward_again(embedder, texts)
    assert np.array_equal(served, again)
    assert choices.shape == (4, 10, 2048, 2)
    assert (choices[:, 1, :40] >= 0).all() and (choices[:, 1, 40:] == -1).all()
    assert (choices[:, 6, :2000] >= 0).all() and (choices[:, 6, 2000:] == -1).all()


def test_work_counts_are_the_issues_arithmetic():
    config = load_cell(CELL).config
    assert work_gqa.layer_windows(config) == [4096, 4096, 4096, None]
    assert work_gqa.experts_a_token_here(config) == 1.0
    # a 16,384-token row: 8.8 TFLOP in the full layer, 3.85 in each window layer
    full = 4 * 128 * 128 * work_gqa.pairs_allowed(16384, None)
    window = 4 * 128 * 128 * work_gqa.pairs_allowed(16384, 4096)
    assert 8.7e12 < full < 8.9e12 and 3.8e12 < window < 3.9e12
    assert work_gqa.attention_flops(config, 16384) == full + 3 * window
    # per token and layer: attention 142.6M, four shared experts 201.3M, one routed expert 50.3M, the router 0.5M
    per_token = work_gqa.forward_flops(config, 1) - work_gqa.attention_flops(config, 1)
    assert per_token == pytest.approx(2 * 4 * (142.6e6 + 201.3e6 + 50.3e6 + 0.5e6), rel=2e-3)
    assert 50e12 < 16384 * per_token < 53e12  # "50 TFLOP of matmuls"
    weights = 4 * 3 * 16 * 4096 * 4096 * 2
    assert work_gqa.expert_matmul_bytes(config, 0) == weights
    assert work_gqa.expert_matmul_flops(config, 100) == 6 * 4096 * 4096 * 100 * 4
    assert work_gqa.attention_bytes(config, 10) == 10 * 4 * 2 * (2 * 128 * 128 + 2 * 8 * 128)


def test_share_readers_return_nothing_without_a_chip_or_a_match():
    config = load_cell(CELL).config
    ticks = [{"encoder_tokens": [5000, 9000, 5000]}]
    trace = types.SimpleNamespace(window=(0.0, 1.0), device_ops={"/device:TPU:0": [(0.1, 0.3, "fusion.1")]})
    rehearsal = types.SimpleNamespace(peaks=None, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert window_mfu_gqa.reduce(rehearsal) is None
    assert op_roofline_gqa.reduce(rehearsal, patterns=["fusion"], calls="attn_block") is None
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    chip = types.SimpleNamespace(peaks=peaks, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert op_roofline_gqa.reduce(chip, patterns=["block_causal_attention"], calls="attn_block") is None
    share, extra = op_roofline_gqa.reduce(chip, patterns=["^fusion"], calls="attn_block")
    flops = sum(work_gqa.attention_flops(config, t) for t in (5000, 9000, 5000))  # sequence by sequence
    assert extra["bound"] == "compute" and share == pytest.approx(100 * (flops / 197e12) / 0.2)
    share, extra = op_roofline_gqa.reduce(chip, patterns=["^fusion"], calls="moe_experts")
    least = sum(
        max(work_gqa.expert_matmul_flops(config, t) / 197e12, work_gqa.expert_matmul_bytes(config, t) / 819e9)
        for t in (14000, 5000)  # batch by batch: the tick's documents, then the probe
    )
    assert share == pytest.approx(100 * least / 0.2)
    assert 0 < window_mfu_gqa.reduce(chip) < 100
