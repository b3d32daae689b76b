"""The delta-rule cell's own pieces: the rehearsal runs and reads every new
metric's span and counter, the fp8 control and the no-carry control are not
``correct`` on three seeds, a timed path broken underneath (a scan that
forgets its state, one that drops the delta term) likewise, a program
without the kinds is refused at once, the work counts are the issue's
arithmetic, and the share readers read nothing without a chip or a match."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, study_controls
from benchmarks.harness import work_gdn
from benchmarks.harness.loader import load_cell
from benchmarks.reducers import op_roofline_gdn, window_mfu_gdn

CELL = "qwen3-next-80b-a3b.gdn-paper-ingest"


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
        assert metrics[f"{name}.gdn-paper-ingest"]["value"] > 0
    # the shares of a peak, the counters' ratios and the device's scopes are a chip's to report
    for name in (
        "step_mfu.gdn-paper-ingest", "gdn_scan_roofline", "moe_experts_roofline.gdn-paper-ingest",
        "gdn_chunks_useful_pct.gdn-paper-ingest", "attn_pairs_useful_pct.gdn-paper-ingest",
        "gdn_around_scan_ms.gdn-paper-ingest", "moe_around_ms.gdn-paper-ingest",
    ):
        assert name not in metrics
    from pathway_tpu.observability.tracing import get_tracer

    forwards = [r for r in get_tracer().spans() if r.name == "embed.forward" and r.attributes.get("trunk") == "qwen3-next-80b-a3b"]
    assert forwards
    for r in forwards:
        a = r.attributes
        assert a["gdn_chunks_visited"] >= a["gdn_chunks_useful"] > 0 and a["gdn_chunks_useful"] % 3 == 0
        assert a["attn_pairs_visited"] >= a["attn_pairs_allowed"] > 0 and "ssm_chunks_useful" not in a
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
        assert line["no_carry"]["vec_err"] > 10 * line["program"]["vec_err"]


def _forgetful(sound):
    def scan(q, k, v, g, beta, chunk=64):
        """Every chunk from a zero state."""
        parts = [sound(*(a[:, i : i + chunk] for a in (q, k, v, g, beta)), chunk) for i in range(0, v.shape[1], chunk)]
        return jnp.concatenate(parts, axis=1)

    return scan


def _without_the_delta(sound):
    def scan(q, k, v, g, beta, chunk=64):
        """S_t = e^g_t S_{t-1} + beta_t k_t v_t^T: decayed linear attention, the read-back dropped."""
        group = v.shape[2] // q.shape[2]
        q, k = (jnp.repeat(a, group, axis=2).astype(jnp.float32) for a in (q, k))

        def step(state, at):
            q_t, k_t, v_t, g_t, b_t = at
            state = jnp.exp(g_t)[..., None, None] * state + (b_t[..., None] * k_t)[..., :, None] * v_t[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

        first = jnp.zeros(v.shape[:1] + v.shape[2:3] + (q.shape[3], v.shape[3]), jnp.float32)
        moved = (jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, g, beta))
        return jnp.moveaxis(jax.lax.scan(step, first, tuple(moved))[1], 0, 1).astype(v.dtype)

    return scan


@pytest.mark.parametrize("broken", [_forgetful, _without_the_delta], ids=["forgets_the_state", "drops_the_delta"])
def test_a_broken_scan_is_not_correct(capsys, monkeypatch, broken):
    """The timed path broken underneath."""
    from pathway_tpu.ops import gated_delta

    monkeypatch.setattr(gated_delta, "scan", broken(gated_delta.scan))
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)
    assert last["compared"]["replay_err"]["value"] == 0 and last["compared"]["stale_probes"]["value"] == 0


@pytest.mark.parametrize("kind", ["gated_deltanet", "gqa_gated"])
def test_a_program_without_a_kind_is_refused_at_once(monkeypatch, kind):
    from benchmarks.harness import sut_gdn
    from pathway_tpu.xpacks.llm import _trunk

    cell = load_cell(CELL, rehearse=True)
    monkeypatch.setattr(_trunk, "ATTENTION", {k: v for k, v in _trunk.ATTENTION.items() if k != kind})
    with pytest.raises(SystemExit, match=kind):
        sut_gdn.build_embedder(cell.config, cell.config_name)


def test_the_cell_is_the_issues():
    cell = load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "gdn_paper_ingest_ticks"
    paper = load_cell("granite-4.0-h-small.paper-ingest").traffic
    for key in ("ticks", "passes", "tick_size", "words", "vocabulary", "shape_seed", "check_ticks"):
        assert cell.traffic[key] == paper[key]  # the same 64 documents and the same plan
    names = {m["name"] for m in cell.per_layer}
    assert {"gdn_scan_roofline", "step_mfu.gdn-paper-ingest", "gdn_chunks_useful_pct.gdn-paper-ingest"} <= names
    assert {"gdn_around_scan_ms.gdn-paper-ingest", "moe_experts_roofline.gdn-paper-ingest", "device_idle_pct.gdn-paper-ingest"} <= names
    assert len(names) == 17 and [m["name"] for m in cell.end_to_end] == ["ingest_docs_per_s", "setup_s"]
    assert cell.config["num_hidden_layers"] == 4 and cell.config["experts_held"] == [0, 256]
    assert cell.config["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}


def test_work_counts_are_the_issues_arithmetic():
    config = load_cell(CELL).config
    assert work_gdn.experts_a_token_here(config) == 5.0
    scan = work_gdn.scan_flops(config, 1000) / 1000 / 3
    assert scan == 6 * 32 * 128 * 128 == 3_145_728  # 3.15 M a token and layer, at the recurrent minimum
    assert work_gdn.linear_layer_flops(config) + scan == 70_582_272  # 70.58 M a DeltaNet layer
    assert work_gdn.full_layer_flops(config) == 54_525_952 and work_gdn.ffn_flops(config) == 39_849_984
    per_token = (work_gdn.forward_flops(config, 1000) - work_gdn.attention_flops(config, 1000)) / 1000
    assert per_token == 3 * 70_582_272 + 54_525_952 + 4 * 39_849_984 == 425_672_704  # 425.67 M a real token
    assert work_gdn.attention_flops(config, 16384) == 16384 * 16384 * 16385 // 2  # 16,384 FLOP an allowed pair
    assert work_gdn.scan_bytes(config, 1) == 3 * 24_832  # q, k, v, o at bfloat16, g and beta float32
    assert work_gdn.expert_matmul_bytes(config, 0) == 4 * 3 * 256 * 2048 * 512 * 2
    assert work_gdn.expert_matmul_flops(config, 100) == 6 * 2048 * 512 * 5 * 100 * 4


def test_share_readers_return_nothing_without_a_chip_or_a_match():
    config = load_cell(CELL).config
    ticks = [{"encoder_tokens": [5000, 9000, 5000]}]
    trace = types.SimpleNamespace(window=(0.0, 1.0), device_ops={"/device:TPU:0": [(0.1, 0.3, "fusion.1")]})
    rehearsal = types.SimpleNamespace(peaks=None, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert window_mfu_gdn.reduce(rehearsal) is None
    assert op_roofline_gdn.reduce(rehearsal, patterns=["fusion"], calls="gdn_scan") is None
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    chip = types.SimpleNamespace(peaks=peaks, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert op_roofline_gdn.reduce(chip, patterns=["gated_delta_chunk_scan"], calls="gdn_scan") is None
    share, extra = op_roofline_gdn.reduce(chip, patterns=["^fusion"], calls="gdn_scan")
    least = sum(  # sequence by sequence; the recurrent minimum moves more bytes than its multiplies take time
        max(work_gdn.scan_flops(config, t) / 197e12, work_gdn.scan_bytes(config, t) / 819e9) for t in (5000, 9000, 5000)
    )
    assert extra["bound"] == "memory" and share == pytest.approx(100 * least / 0.2)
    share, extra = op_roofline_gdn.reduce(chip, patterns=["^fusion"], calls="moe_experts")
    least = sum(
        max(work_gdn.expert_matmul_flops(config, t) / 197e12, work_gdn.expert_matmul_bytes(config, t) / 819e9)
        for t in (14000, 5000)  # batch by batch: the tick's documents, then the probe
    )
    assert extra["bound"] == "memory" and share == pytest.approx(100 * least / 0.2)
    assert 0 < window_mfu_gdn.reduce(chip) < 100
    tokens = [5000, 9000, 5000]
    assert window_mfu_gdn.reduce(chip) == pytest.approx(100 * sum(work_gdn.forward_flops(config, t) for t in tokens) / 197e12)
    assert np.isfinite(share)
