"""MiMo-V2.5 as the trunk (``model_type`` ``mimo_v2``): window layers with a
sink a query head and full layers of split widths, experts without a shared
one, against the benchmark's plain reference (``reference_swa.py``) at the
configuration's rehearse sizes on the CPU."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_swa as ref
from benchmarks.harness import weights_swa
from pathway_tpu.observability import device_scopes
from pathway_tpu.ops import block_attention, moe
from pathway_tpu.xpacks.llm import _trunk
from pathway_tpu.xpacks.llm._trunk import TrunkConfig, TrunkRuntime
from tests.test_trunk import BF16_TOL, F32_TOL, batch, toy_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SWA_FILE = os.path.join(ROOT, "benchmarks", "configs", "mimo-v2.5.json")


def swa_dict(**changes) -> dict:
    return toy_dict(SWA_FILE, **changes)


def swa_params(config, seed, dtype=jnp.float32):
    """The benchmark's weights (logits of standard deviation 4, sinks that hold a share of a row)."""
    return weights_swa.make_params(jax.eval_shape(lambda: _trunk.init_params(config, 0, dtype)), seed)


@pytest.fixture(scope="module")
def swa():
    body = swa_dict()
    return body, TrunkConfig.from_dict(body, name="toy-swa")


@pytest.fixture(scope="module")
def swa_runtime(swa):
    _body, config = swa
    runtime = TrunkRuntime(config, max_len=128, seed=7, dtype=jnp.float32)
    runtime.params = swa_params(config, 7)
    return runtime


def reference_rows(params, ids, mask, body, **kw):
    return np.stack([np.asarray(ref.encode(params, row, int(m.sum()), body, **kw)[0]) for row, m in zip(ids, mask)])


def test_the_mimo_config_file_reads_as_published():
    config = TrunkConfig.from_file(SWA_FILE, name="mimo-v2.5")
    table = config.layer_table()
    assert [k.attention for k in table] == ["gqa_partial"] + ["swa_sink"] * 4 + ["gqa_partial", "swa_sink"]
    assert [k.ffn for k in table] == ["dense"] + ["moe"] * 6 and {k.residual for k in table} == {"add"}
    assert config.norm_kind == "rms" and config.norm_eps == 1e-5 and config.layer_norm_eps is None
    assert (config.hidden_size, config.num_attention_heads, config.head_dim, config.sliding_window) == (4096, 64, 192, 128)
    assert (config.num_key_value_heads, config.swa_num_key_value_heads, config.v_head_dim, config.swa_v_head_dim) == (4, 8, 128, 128)
    assert (config.rope_theta, config.swa_rope_theta, int(config.head_dim * config.rotary_pct)) == (10_000_000, 10_000, 64)
    assert (config.n_routed_experts, config.held, config.num_experts_per_tok, config.n_shared_experts) == (256, (0, 16), 8, 0)
    assert (config.routed_scaling_factor, config.scoring_func, config.topk_method, config.attention_value_scale) == (1.0, "sigmoid", "noaux_tc", 0.707)
    shapes = _trunk.param_shapes(config)
    ffn, attn = shapes["layers"][1]["ffn"], shapes["layers"][1]["attn"]
    assert ffn["router"][0] == (4096, 256) and ffn["bias"][0] == (256,) and "shared" not in ffn and "shared_gate" not in ffn
    assert attn["wq"][0] == (4096, 64, 192) and attn["wk"][0] == (4096, 8, 192) and attn["wv"][0] == (4096, 8, 128)
    assert attn["wo"][0] == (64, 128, 4096) and attn["sinks"][0] == (64,)
    full = shapes["layers"][5]["attn"]
    assert full["wk"][0] == (4096, 4, 192) and full["wv"][0] == (4096, 4, 128) and "sinks" not in full
    assert shapes["layers"][0]["ffn"]["w_gate"][0] == (4096, 16384) and shapes["embed"][0] == (19072, 4096)
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(template) == 3_351_836_480
    assert [count(layer) for layer in template["layers"]] == [290_463_744] + [498_082_112] * 4 + [492_839_168, 498_082_112]
    assert count(template["layers"][1]["attn"]) == 94_371_904 and count(template["layers"][0]["attn"]) == 89_128_960


@pytest.mark.parametrize(
    "key, value",
    [
        ("n_group", 2), ("add_full_attention_sink_bias", True), ("attention_bias", True), ("hybrid_block_size", 4),
        ("add_swa_attention_sink_bias", False), ("attention_projection_layout", "split_qkv"),
    ],
)
def test_mimo_keys_without_a_block_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        TrunkConfig.from_dict(swa_dict(**{key: value}), name="toy-swa")


def test_a_pattern_value_without_a_kind_is_named():
    config = TrunkConfig.from_dict(swa_dict(hybrid_layer_pattern=[0, 1, 2, 1, 1, 0, 1]), name="toy-swa")
    with pytest.raises(NotImplementedError, match="hybrid_layer_pattern 2"):
        _trunk.param_shapes(config)


@pytest.mark.parametrize("rows, seed", [(3, 0), (5, 1), (8, 2)])
def test_swa_forward_float32_matches_the_reference_over_several_windows(swa, swa_runtime, rows, seed):
    body, config = swa
    ids, mask = batch(rows, 128, seed)  # row 0 fills 128 positions: 8 windows of 16
    assert mask.sum(axis=1).max() == 8 * config.sliding_window
    got, info = swa_runtime.forward(ids, mask)
    want = reference_rows(swa_runtime.params, ids, mask, body)
    assert np.linalg.norm(got - want, axis=1).max() < F32_TOL
    lengths = mask.sum(axis=1).astype(int)
    window = sum(block_attention.pairs_allowed(int(t), 16) for t in lengths)
    assert info["attn_window_pairs_allowed"] == 5 * window
    assert info["attn_pairs_allowed"] == 5 * window + 2 * sum(block_attention.pairs_allowed(int(t), None) for t in lengths)
    visited = sum(block_attention.pairs_visited(128, 16, tokens=int(t)) for t in lengths)
    assert info["attn_window_pairs_visited"] == 5 * visited and info["attn_pairs_visited"] > info["attn_window_pairs_visited"]
    # a reference without its sinks is somebody else's vectors
    dropped = reference_rows(swa_runtime.params, ids[:2], mask[:2], body, mode="no_sink")
    assert np.linalg.norm(dropped - want[:2], axis=1).min() > 50 * F32_TOL


def test_swa_forward_bfloat16_follows_its_own_experts(swa):
    body, config = swa
    runtime = TrunkRuntime(config, max_len=128)
    runtime.params = swa_params(config, 8, jnp.bfloat16)
    ids, mask = batch(4, 64, 3)
    got, info = runtime.forward(ids, mask, routing=True)
    choice = info["expert_choice"]
    assert choice.shape == (6, 4, 64, 3) and ((choice >= 0).all(axis=-1) == (mask > 0)[None]).all()
    want = np.stack(
        [np.asarray(ref.encode(runtime.params, ids[i], int(mask[i].sum()), body, forced=choice[:, i])[0]) for i in range(4)]
    )
    assert np.linalg.norm(got - want, axis=1).max() < BF16_TOL


def _sinkless(attention):
    def without(q, k, v, *, sinks=None, **kw):
        return attention(q, k, v, **kw)

    return without


CHANGES = {  # what the file would be read as, were a piece lost or read wrongly
    "window_widened": {"sliding_window": 32},
    "value_scale_left_out": {"attention_value_scale": 1.0},
    "window_theta": {"swa_rope_theta": 10_000_000},
    "full_theta": {"rope_theta": 10_000},
    "layer_norm": {"layer_norm_eps": 1e-5},
}


@pytest.mark.parametrize("change", ["sink_dropped", *CHANGES])
def test_each_piece_changes_the_result(swa, swa_runtime, change, monkeypatch):
    """A kernel that drops the sink, a wider window, the value scale left
    out, the other kind's theta on either kind and ``layernorm_epsilon``
    read as a layer norm's each move the vectors, and (where the change is a
    key's) the reference moves with them."""
    body, config = swa
    ids, mask = batch(2, 128, 4)
    before = swa_runtime.forward_ids(ids, mask)
    changed = dict(body, **CHANGES.get(change, {}))
    runtime = TrunkRuntime(TrunkConfig.from_dict(changed, name="toy-swa"), max_len=128, dtype=jnp.float32)
    runtime.params = swa_runtime.params
    if change == "sink_dropped":
        monkeypatch.setattr(block_attention, "attention", _sinkless(block_attention.attention))
    after = runtime.forward_ids(ids, mask)
    assert np.linalg.norm(after - before, axis=1).min() > 1e-3
    if change == "sink_dropped":  # the reference without its sinks
        assert np.linalg.norm(after - reference_rows(runtime.params, ids, mask, body, mode="no_sink"), axis=1).max() < F32_TOL
    elif change != "layer_norm":
        assert np.linalg.norm(after - reference_rows(runtime.params, ids, mask, changed), axis=1).max() < F32_TOL


def test_swa_padding_and_companions_change_no_vector(swa_runtime):
    ids, mask = batch(rows=3, width=64, seed=5)
    together = swa_runtime.forward_ids(ids, mask)
    for i in range(3):
        alone = swa_runtime.forward_ids(ids[i : i + 1], mask[i : i + 1])
        assert np.abs(alone[0] - together[i]).max() < 1e-5
    wide, info = swa_runtime.forward(np.pad(ids, ((0, 0), (0, 64))), np.pad(mask, ((0, 0), (0, 64))))
    assert info["len_bucket"] == 128 and np.abs(wide - together).max() < 1e-5


@pytest.mark.parametrize("layer, kind", [(1, "window"), (5, "full")])
def test_the_two_shares_of_a_mimo_layer_add_up_to_the_uncut_layer(layer, kind):
    """Sixteen experts over two shares of 8 (experts 0-7 and 8-15 at toy
    size; the deployment's are sixteen shares of 16 of 256): what the
    shares' routed parts give, with the attention, its sinks, the router and
    x counted once, is the uncut layer as the reference computes it."""
    whole_body = swa_dict(n_routed_experts=16, experts_held=None, published={})
    whole = TrunkConfig.from_dict(whole_body, name="uncut")
    assert whole.held == (0, 16) and whole.n_routed_experts == 16 and whole.n_shared_experts == 0
    params = swa_params(whole, 17)
    p = params["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, whole.hidden_size), jnp.float32)
    block = _trunk.ATTENTION[whole.layer_table()[layer].attention]
    assert block.scope == ("trunk.swa_sink" if kind == "window" else "trunk.gqa_partial")
    ctx = {
        "rope_swa": _trunk.interleaved_rope_tables(whole, 40, 8, whole.swa_rope_theta),
        "rope_full": _trunk.interleaved_rope_tables(whole, 40, 8),
    }
    mixed = np.asarray(block.apply(p["attn"], _trunk.norm(x, p["attn_norm"], whole), whole, ctx))[0]
    after = x + mixed[None]
    flat = _trunk.norm(after, p["ffn_norm"], whole).reshape(-1, whole.hidden_size)
    valid = jnp.ones(flat.shape[0], bool)
    parts = []
    for first in (0, 8):
        routed, _counts, _choice = moe.expert_layer(
            flat, valid, p["ffn"]["router"], p["ffn"]["bias"], p["ffn"]["w_gate"][first : first + 8],
            p["ffn"]["w_up"][first : first + 8], p["ffn"]["w_down"][first : first + 8],
            top_k=3, scale=1.0, experts_held=(first, 8),
        )
        parts.append(np.asarray(routed))
    assert all(np.abs(part).max() > 0 for part in parts)
    with jax.default_matmul_precision("highest"):
        want, _scores = ref.layer(p, x[0], None, whole_body, kind, True)
    assert np.abs(np.asarray(after[0]) + sum(parts) - np.asarray(want)).max() < 2e-4
    # and a cut layer is the program's own layer on its share
    cut = TrunkConfig.from_dict(swa_dict(experts_held=[8, 8]), name="cut")
    held = dict(p, ffn={k: (v[8:16] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p["ffn"].items()})
    ctx.update(valid=valid, expert_counts=[], expert_choice=[])

    def attend(p_attn, u):
        return block.apply(p_attn, u, cut, ctx)

    def feed(p_ffn, u):
        return _trunk.FFN["moe"].apply(p_ffn, u, cut, ctx)

    got = np.asarray(_trunk.RESIDUAL["add"].layer(held, x, attend, feed, cut))[0]
    assert np.abs(got - (np.asarray(after[0]) + parts[1])).max() < 2e-4


def test_the_forward_names_its_attention_parts(swa):
    """Every instruction of the forward has a scope of the vocabulary; the
    two kinds open ``trunk.attn.qkv``, ``.kernel`` and ``.out`` inside their
    own, and an expert layer without a shared expert opens no
    ``trunk.moe.shared``."""
    _body, config = swa
    runtime = TrunkRuntime(config, max_len=64, seed=1)
    ids, mask = batch(3, 32, 6)
    runtime.forward(ids, mask)
    ((_label, program, args, kwargs),) = runtime.device_programs()
    text = program.lower(*args, **kwargs).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    named = {device_scopes.scope_of(path) for path in paths}
    assert {"trunk.attn.qkv", "trunk.attn.kernel", "trunk.attn.out"} <= named
    assert "trunk.moe.shared" not in named and named <= set(device_scopes.VOCABULARY) | {device_scopes.NO_SCOPE}
    for kind in ("trunk.swa_sink", "trunk.gqa_partial"):  # the kind's scope holds the three
        assert all(any(f"{kind}/trunk.attn.{part}" in path for path in paths) for part in ("qkv", "kernel", "out"))
