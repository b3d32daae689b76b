"""The trunk cell's own pieces: a broken expert layer, a router that passes
over its best expert and a residual blind to its input are not ``correct``,
the seeded weights are the benchmark's, a traced rehearsal reads the
program's spans, the work counts are the configuration's arithmetic, and
the readers that find nothing to read return nothing."""

import json
import types

import pytest

from benchmarks import run
from benchmarks.harness import work_trunk
from benchmarks.harness.loader import load_cell
from benchmarks.reducers import op_roofline, window_mfu_trunk

CELL = "xing4-29b-a4b.chunk-ingest"


def rehearse(capsys, trace=0, seed=3000000019):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "1", "--trace", str(trace), "--rehearse"]
    run.main(argv)
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def over(last) -> set:
    return {name for name, c in last["compared"].items() if c["value"] > c["limit"]}


def test_a_router_that_passes_over_its_best_expert_is_not_correct(capsys, monkeypatch):
    """The choice is ranks 2..k+1 of the corrected scores in place of 1..k:
    the reference follows it, so the vectors agree, and ``route_gap`` alone
    has to say that these are not the experts to send a token to."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops import moe

    def second_best(h, router, bias, *, top_k, scale, normalise=True):
        scores = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32), precision="highest"))
        _, ranked = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k + 1)
        choice = ranked[:, 1:]
        picked = jnp.take_along_axis(scores, choice, axis=1)
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
        return picked * scale, choice.astype(jnp.int32)

    monkeypatch.setattr(moe, "route", second_best)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and over(last) == {"route_gap"}


def test_a_residual_blind_to_its_input_is_not_correct(capsys, monkeypatch):
    """The residual's coefficient projection reads nothing (zeros in its
    place): the coefficients are their biases for every token. The seeded
    scalars a_* are large enough for the comparison to see it."""
    import jax.numpy as jnp

    from pathway_tpu.xpacks.llm import _trunk

    sound = _trunk.mhc_coefficients

    def blind(p, streams, c):
        return sound(dict(p, proj=jnp.zeros_like(p["proj"])), streams, c)

    monkeypatch.setattr(_trunk, "mhc_coefficients", blind)
    last, _err = rehearse(capsys)
    assert last["correct"] is False and "vec_err" in over(last)
    assert last["compared"]["vec_err"]["value"] > 3 * last["compared"]["vec_err"]["limit"]


def test_seeded_weights_are_the_benchmarks_own():
    """The program gives the tree's shape; every value is ``weights_trunk``'s."""
    import jax
    import numpy as np

    from benchmarks.harness import sut_trunk, weights_trunk

    cell = load_cell(CELL, rehearse=True)
    embedder = sut_trunk.build_embedder(cell.config, cell.config_name)
    params = sut_trunk.seed_weights(embedder, 3000000019)
    assert embedder.runtime.params is params
    from pathway_tpu.xpacks.llm._trunk import init_params

    own = init_params(embedder.runtime.config, 0)
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(params)
    for made, programs in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(own)):
        assert made.shape == programs.shape and made.dtype == programs.dtype
    layer = params["layers"][1]
    assert np.allclose(np.asarray(layer["ffn_res"]["alpha"]), weights_trunk.MHC_ALPHA)
    assert 0.003 < float(np.asarray(layer["ffn"]["bias"]).std()) < 0.03
    experts = np.asarray(layer["ffn"]["w_gate"], np.float32)  # [experts, in, out]: fan-in is in
    assert abs(experts.std() * np.sqrt(experts.shape[1]) - 1) < 0.05
    again = sut_trunk.seed_weights(embedder, 3000000019)
    other = sut_trunk.seed_weights(embedder, 3000000020)
    assert np.array_equal(np.asarray(again["embed"], np.float32), np.asarray(params["embed"], np.float32))
    assert not np.array_equal(np.asarray(other["embed"], np.float32), np.asarray(params["embed"], np.float32))
    with pytest.raises(ValueError, match="no rule for the leaf"):
        weights_trunk.rule_of("layers/0/attn/w_new", (4, 4))


def test_a_traced_rehearsal_reads_the_programs_spans(capsys):
    """``test_program_spans.py``'s traced rehearsal, for this cell's names."""
    last, _err = rehearse(capsys, trace=1, seed=2147483659)
    metrics = last["metrics"]
    assert last["correct"] is True
    for name in ("tokenize_ms", "forward_ms", "embed_ms", "index_refresh_ms", "corpus_upload_ms", "corpus_prepare_ms"):
        assert metrics[f"{name}.chunk-ingest"]["value"] > 0
    # the inside of a call is less than the harness's span around the call
    inside = metrics["tokenize_ms.chunk-ingest"]["value"] + metrics["forward_ms.chunk-ingest"]["value"]
    assert inside < metrics["embed_ms.chunk-ingest"]["value"]
    assert metrics["corpus_upload_ms.chunk-ingest"]["bytes_per_tick"] > 0
    refresh = metrics["corpus_upload_ms.chunk-ingest"]["value"] + metrics["corpus_prepare_ms.chunk-ingest"]["value"]
    assert refresh < metrics["index_refresh_ms.chunk-ingest"]["value"]
    # the expert rows ride on the program's forward span
    from pathway_tpu.observability.tracing import get_tracer

    forwards = [r for r in get_tracer().spans() if r.name == "embed.forward" and "trunk" in r.attributes]
    assert forwards and all(
        r.attributes["expert_rows_computed"] >= r.attributes["expert_rows_useful"] > 0 for r in forwards
    )


def test_unnormalised_combine_weights_are_not_correct(capsys, monkeypatch):
    """The expert layer's combine weights left unnormalised (s[choice] in
    place of s[choice] / sum): every routed output is some 1.5 times too
    large, and the comparison has to say so."""
    from pathway_tpu.ops import moe

    sound = moe.route

    def unnormalised(h, router, bias, *, top_k, scale, normalise=True):
        return sound(h, router, bias, top_k=top_k, scale=scale, normalise=False)

    monkeypatch.setattr(moe, "route", unnormalised)
    argv = ["--workload", CELL, "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse"]
    run.main(argv)
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["compared"]["vec_err"]["value"] > last["compared"]["vec_err"]["limit"]
    assert last["compared"]["stale_probes"]["value"] == 0  # the index still holds together


def test_work_counts_are_the_issues_arithmetic():
    config = load_cell(CELL).config
    assert work_trunk.expert_layers(config) == 5
    per_token = work_trunk.forward_flops(config, 1) - 6 * 32 * 320 * 2  # less its own attention
    # ISSUE 28: 57 + 198 in the dense layer, 57 + 22 + 88 + 0.5 in each expert layer, the residual's 1.6
    assert 1.08e9 < per_token < 1.13e9
    tick = [380] * 32
    whole = sum(work_trunk.forward_flops(config, t) for t in tick)
    assert 13.0e12 < whole < 14.5e12  # "13.7 TFLOP a tick"
    routed = work_trunk.expert_matmul_flops(config, sum(tick))
    assert routed == pytest.approx(6 * 3584 * 1024 * 4 * 12160 * 5)
    weights = 5 * 3 * 64 * 3584 * 1024 * 2
    assert work_trunk.expert_matmul_bytes(config, 0) == weights  # 1.41 GB a layer
    assert work_trunk.expert_matmul_bytes(config, 100) == weights + 5 * 2 * 100 * 4 * 3584 * 2
    assert work_trunk.tick_forwards({"encoder_tokens": [5, 6, 7, 6]}) == [[5, 6, 7], [6]]


def test_share_readers_return_nothing_without_a_chip_or_a_match():
    config = load_cell(CELL).config
    ticks = [{"encoder_tokens": [100, 200, 100]}]
    trace = types.SimpleNamespace(window=(0.0, 1.0), device_ops={"/device:TPU:0": [(0.1, 0.2, "fusion.1")]})
    rehearsal = types.SimpleNamespace(peaks=None, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert window_mfu_trunk.reduce(rehearsal) is None
    assert op_roofline.reduce(rehearsal, patterns=["fusion"], calls="moe_experts") is None
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    chip = types.SimpleNamespace(peaks=peaks, ticks=ticks, trace=trace, config=config, seconds=1.0, chips=1)
    assert op_roofline.reduce(chip, patterns=["moe_grouped_matmul"], calls="moe_experts") is None
    share, extra = op_roofline.reduce(chip, patterns=["^fusion"], calls="moe_experts")
    # two forwards (300 and 100 tokens), each bound by reading 5 layers of expert weights
    nbytes = sum(work_trunk.expert_matmul_bytes(config, tokens) for tokens in (300, 100))
    assert extra["bound"] == "memory" and extra["device_ms_per_tick"] == pytest.approx(100.0)
    assert share == pytest.approx(100 * (nbytes / 819e9) / 0.1)
    assert 0 < window_mfu_trunk.reduce(chip) < 100
