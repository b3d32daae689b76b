"""The trunk runtime (``xpacks/llm/_trunk.py``, ``ops/moe.py``) against the
benchmark's plain reference, at the configuration's rehearse sizes on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_trunk as ref
from pathway_tpu.ops import moe
from pathway_tpu.xpacks.llm import _trunk
from pathway_tpu.xpacks.llm._trunk import TrunkConfig, TrunkRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs", "xing4-29b-a4b.json")
F32_TOL = 2e-4  # float32 both sides: rounding order only
BF16_TOL = 0.08  # bfloat16 program, the reference following its choice of experts


def rehearse_dict() -> dict:
    with open(CONFIG_FILE, encoding="utf-8") as f:
        body = json.load(f)
    toy = body.pop("rehearse")
    body.update({k: v for k, v in toy.items() if not isinstance(v, dict)})
    return body


@pytest.fixture(scope="module")
def toy():
    body = rehearse_dict()
    return body, TrunkConfig.from_dict(body, name="toy")


def batch(rows=5, width=32, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(3, width + 1, size=rows)
    lengths[0] = width  # one row fills the bucket
    mask = (np.arange(width)[None, :] < lengths[:, None]).astype(np.float32)
    ids = (rng.integers(2, vocab, size=(rows, width)) * mask).astype(np.int32)
    return ids, mask


def f32_tree(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# -- (a) the whole forward -------------------------------------------------------


@pytest.fixture(scope="module")
def runtimes(toy):
    """One runtime a precision: cases of one padded shape share its compiled forward."""
    _body, config = toy
    return {
        "float32": TrunkRuntime(config, max_len=64, seed=3, dtype=jnp.float32),
        "bfloat16": TrunkRuntime(config, max_len=64, seed=4),
    }


@pytest.mark.parametrize("rows, seed", [(5, 0), (3, 1), (8, 2)])
def test_forward_float32_matches_the_reference(toy, runtimes, rows, seed):
    body, _config = toy
    runtime = runtimes["float32"]
    ids, mask = batch(rows, 32, seed)
    got, info = runtime.forward(ids, mask)
    want, _scores = ref.encode(runtime.params, ids, mask, body)
    assert got.shape == (rows, 64) and info["batch_bucket"] == 8 and info["tokens_padded"] == 256
    assert np.abs(np.linalg.norm(got, axis=1) - 1).max() < 1e-5
    assert np.linalg.norm(got - np.asarray(want), axis=1).max() < F32_TOL


@pytest.mark.parametrize("seed", [1, 2])
def test_forward_bfloat16_is_within_the_bf16_tolerance(toy, runtimes, seed):
    body, _config = toy
    runtime = runtimes["bfloat16"]
    ids, mask = batch(rows=8, seed=seed)
    got, info = runtime.forward(ids, mask, routing=True)
    choice = info["expert_choice"]  # [expert layers, rows, positions, k]; -1 at padding
    assert choice.shape == (2, 8, 32, 2) and ((choice >= 0).all(axis=-1) == (mask > 0)[None]).all()
    # the reference follows the program's experts: what is left is arithmetic
    want, scores = ref.encode(runtime.params, ids, mask, body, forced=choice)
    assert np.linalg.norm(got - np.asarray(want), axis=1).max() < BF16_TOL
    # and the experts chosen are, by the reference's own scores, the best or nearly so
    scores = np.asarray(scores)
    kth = np.sort(scores, axis=-1)[..., -2]
    lowest = np.take_along_axis(scores, np.maximum(choice, 0), axis=-1).min(axis=-1)
    assert np.where(mask[None] > 0, kth - lowest, 0).max() < 0.03


def test_config_file_gives_the_cut_layer_table():
    config = TrunkConfig.from_file(CONFIG_FILE, name="xing4-29b-a4b")
    table = config.layer_table()
    assert [k.ffn for k in table] == ["dense"] + ["moe"] * 5
    assert {k.attention for k in table} == {"mla"} and {k.residual for k in table} == {"mhc"}
    assert config.hidden_size == 3584 and config.held == (0, 64) and config.rope_scaling.factor == 64
    shapes = _trunk.param_shapes(config)
    sizes = [int(np.prod(s)) for s, _ in jax.tree_util.tree_leaves(shapes, is_leaf=_trunk._is_leaf)]
    assert 4.30e9 < sum(sizes) < 4.34e9  # ISSUE 28's arithmetic: 4.32 B parameters


def test_an_unknown_kind_is_named():
    config = TrunkConfig.from_dict(rehearse_dict(), hc_mult=1)
    assert config.layer_table()[0].residual == "add"
    with pytest.raises(NotImplementedError, match="'add'"):
        _trunk.param_shapes(config)
    with pytest.raises(ValueError, match="scoring_func"):
        TrunkConfig(scoring_func="softmax")


# -- (b) each block kind alone ---------------------------------------------------


@pytest.fixture(scope="module")
def blocks(toy):
    body, config = toy
    params = f32_tree(_trunk.init_params(config, 11, jnp.float32))
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, config.hidden_size), jnp.float32)
    return body, config, params, h


def test_mla_block(blocks):
    body, config, params, h = blocks
    p = params["layers"][0]["attn"]
    ctx = {"rope": _trunk.rope_tables(config, h.shape[1])}
    got = _trunk.ATTENTION["mla"].apply(p, h, config, ctx)
    want = ref.mla(p, h, body)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    cos, sin = ref.rotary_tables(body, 16)
    assert np.allclose(ctx["rope"][0], cos, atol=1e-6) and np.allclose(ctx["rope"][1], sin, atol=1e-6)
    assert abs(_trunk.softmax_scale(config) - ref.attention_scale(body)) < 1e-9


def test_dense_ffn_block(blocks):
    body, config, params, h = blocks
    p = params["layers"][0]["ffn"]
    got = _trunk.FFN["dense"].apply(p, h, config, {})
    assert np.abs(np.asarray(got) - np.asarray(ref.gated_ffn(p, h))).max() < 1e-4


@pytest.mark.parametrize("layer", [1, 2])
def test_expert_ffn_block(blocks, layer):
    body, config, params, h = blocks
    p = params["layers"][layer]["ffn"]
    ctx = {"valid": jnp.ones(h.shape[0] * h.shape[1], bool), "expert_counts": [], "expert_choice": []}
    got = _trunk.FFN["moe"].apply(p, h, config, ctx)
    want, scores = ref.expert_ffn(p, h.reshape(-1, h.shape[-1]), body)
    assert np.abs(np.asarray(got).reshape(want.shape) - np.asarray(want)).max() < 1e-4
    counts = np.asarray(ctx["expert_counts"][0])
    assert counts.sum() == h.shape[0] * h.shape[1] * config.num_experts_per_tok
    # the choice handed out is the reference's own top-k of its corrected scores
    choice = np.asarray(ctx["expert_choice"][0]).reshape(-1, config.num_experts_per_tok)
    own = np.argsort(np.asarray(scores), axis=-1)[:, -config.num_experts_per_tok :]
    assert (np.sort(choice, axis=-1) == np.sort(own, axis=-1)).all()
    # told to follow another choice, the reference weighs those experts by its own scores
    other = (choice + 1) % config.n_routed_experts
    moved, _scores = ref.expert_ffn(p, h.reshape(-1, h.shape[-1]), body, forced=jnp.asarray(other))
    assert np.abs(np.asarray(moved) - np.asarray(want)).max() > 1e-2


def test_residual_block_and_sinkhorn(blocks):
    body, config, params, h = blocks
    p = params["layers"][1]["ffn_res"]
    n = config.hc_mult
    streams = jax.random.normal(jax.random.PRNGKey(6), (n,) + h.shape, jnp.float32)
    sublayer = lambda u: jnp.tanh(u) * 0.5  # noqa: E731
    got = _trunk.RESIDUAL["mhc"].apply(p, streams, sublayer, config)
    ref_streams = jnp.transpose(streams, (1, 2, 0, 3))
    want = ref.residual(p, ref_streams, sublayer, body)
    assert np.abs(np.asarray(jnp.transpose(got, (1, 2, 0, 3))) - np.asarray(want)).max() < 1e-4
    _pre, _post, h_res = _trunk.mhc_coefficients(p, streams, config)
    h_res = np.asarray(h_res)  # [n, n, B, T]
    assert np.abs(h_res.sum(axis=0) - 1).max() < 1e-4 and np.abs(h_res.sum(axis=1) - 1).max() < 1e-4
    assert h_res.min() > 0 and not np.allclose(h_res, np.swapaxes(h_res, 0, 1), atol=1e-3)
    _pre, _post, want_res = ref.residual_coefficients(p, ref_streams, body)
    assert np.abs(np.transpose(h_res, (2, 3, 0, 1)) - np.asarray(want_res)).max() < 1e-5


# -- (c) padding -----------------------------------------------------------------


def test_right_padding_changes_no_vector(runtimes):
    runtime = runtimes["float32"]
    ids, mask = batch(rows=3, width=32, seed=2)
    narrow = runtime.forward_ids(ids, mask)
    wide_ids, wide_mask = np.pad(ids, ((0, 6), (0, 32))), np.pad(mask, ((0, 6), (0, 32)))
    wide, info = runtime.forward(wide_ids, wide_mask)
    assert info["batch_bucket"] == 16 and info["tokens_padded"] == 16 * 64
    assert np.abs(narrow - wide[:3]).max() < 1e-5


# -- (d) the chip's share of the experts -----------------------------------------


@pytest.mark.parametrize("layer", [1, 2])
def test_expert_shares_add_up_to_the_whole_layer(blocks, layer):
    body, config, params, h = blocks
    p = params["layers"][layer]["ffn"]
    flat = h.reshape(-1, h.shape[-1])
    valid = jnp.ones(flat.shape[0], bool)
    experts = config.n_routed_experts
    half = experts // 2

    def routed(first, count):
        out, counts, _choice = moe.expert_layer(
            flat, valid, p["router"], p["bias"],
            p["w_gate"][first : first + count], p["w_up"][first : first + count],
            p["w_down"][first : first + count],
            top_k=config.num_experts_per_tok, scale=config.routed_scaling_factor,
            experts_held=(first, count),
        )
        return np.asarray(out), np.asarray(counts)

    low, counts_low = routed(0, half)
    high, counts_high = routed(half, experts - half)
    shared = np.asarray(_trunk._gated_ffn(p["shared"], flat))
    whole, _scores = ref.expert_ffn(p, flat, body)
    assert np.abs(low + high + shared - np.asarray(whole)).max() < 1e-4
    assert (counts_low == counts_high).all() and np.abs(low).max() > 0 and np.abs(high).max() > 0
    # the reference is given the same share and computes the same part
    held = {k: p[k][:half] if k.startswith("w_") else p[k] for k in p}
    part, _scores = ref.expert_ffn(held, flat, body, experts_held=(0, half), shared=False)
    assert np.abs(low - np.asarray(part)).max() < 1e-4


# -- (e) the dispatch ------------------------------------------------------------


@pytest.mark.parametrize("tokens, top_k", [(48, 2), (300, 2), (200, 3)])
@pytest.mark.parametrize("routing", ["one_expert", "random"])
def test_dispatch_loses_and_duplicates_no_row(tokens, top_k, routing):
    experts, align = 8, moe.TILE_ROWS
    rng = np.random.default_rng(9)
    if routing == "one_expert":  # every token's first choice is expert 5
        second = rng.integers(0, 5, tokens)
        choice = np.stack([np.full(tokens, 5)] + [(second + j) % 5 for j in range(top_k - 1)], axis=1)
    else:
        choice = np.stack([rng.permutation(experts)[:top_k] for _ in range(tokens)])
    valid = np.ones(tokens, bool)
    valid[tokens - 8 :] = False  # padding positions are routed nowhere
    plan = moe.dispatch(jnp.asarray(choice, jnp.int32), jnp.asarray(valid), experts)
    src, dest = np.asarray(plan.src), np.asarray(plan.dest)
    sizes, counts = np.asarray(plan.group_sizes), np.asarray(plan.counts)
    rows = moe.plan_rows(tokens * top_k, experts)
    assert len(src) == rows and (sizes % align == 0).all()
    assert (counts == np.bincount(choice[valid].reshape(-1), minlength=experts)).all()
    assert (sizes >= counts).all() and (sizes - counts < align).all()
    # every valid pair has one row of its own, in its expert's group, fed by its token
    starts = np.cumsum(sizes) - sizes
    pair_rows = dest[valid].reshape(-1)
    assert len(set(pair_rows)) == valid.sum() * top_k and pair_rows.max() < rows
    for t in np.flatnonzero(valid):
        for j in range(top_k):
            row, expert = dest[t, j], choice[t, j]
            assert src[row] == t and starts[expert] <= row < starts[expert] + counts[expert]
    assert (dest[~valid] == rows).all()
    assert (src < tokens).sum() == valid.sum() * top_k  # every other row is padding


# -- (f) the embedder ------------------------------------------------------------


def test_embedder_without_trunk_builds_the_tree_it_built():
    from benchmarks.harness.weights import make_params
    from pathway_tpu.xpacks.llm._encoder import EncoderRuntime, TransformerEncoder
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    embedder = SentenceTransformerEmbedder(dim=16, depth=1, heads=2, max_len=64)
    assert type(embedder.runtime) is EncoderRuntime
    model = TransformerEncoder(vocab_size=30522, dim=16, depth=1, heads=2, max_len=64)
    want = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.float32))
    got = embedder.runtime.params
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype == jnp.float32 and np.array_equal(np.asarray(a), np.asarray(b))
    seeded = make_params(got, 7)  # the harness's seed_weights contract
    assert jax.tree_util.tree_structure(seeded) == jax.tree_util.tree_structure(got)


def test_embedder_with_trunk_runs_the_same_embed_batch(toy):
    from pathway_tpu.observability import tracing
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    body, config = toy
    embedder = SentenceTransformerEmbedder(trunk=config, max_len=64)
    assert type(embedder.runtime) is TrunkRuntime and embedder.tokenizer.vocab_size == 512
    assert embedder.get_embedding_dimension() == 64
    embedder.runtime.params = _trunk.init_params(embedder.runtime.config, 21)  # settable
    texts = ["one two three", "four", "five six seven eight nine ten eleven"]
    embedder._embed_batch(texts)
    tracing.get_tracer().clear()
    vectors = embedder._embed_batch(texts)
    forward = [r for r in tracing.get_tracer().spans() if r.name == "embed.forward"][-1]
    names = {r.name for r in tracing.get_tracer().spans()}
    assert {"embed.batch", "embed.tokenize", "embed.forward"} <= names
    moe_layers = 2
    assert forward.attributes == {
        "groups": 1, "batch_bucket": 8, "len_bucket": 16, "tokens_real": 14, "tokens_padded": 128,
        "trunk": "toy", "expert_rows_useful": 14 * 2 * moe_layers,
        "expert_rows_computed": forward.attributes["expert_rows_computed"],
        "expert_tokens_max": forward.attributes["expert_tokens_max"],
        "expert_tokens_mean": 14 * 2 / 8,
    }
    assert forward.attributes["expert_rows_computed"] >= forward.attributes["expert_rows_useful"]
    ids, mask = embedder.tokenizer.encode_batch(texts, 64)
    want, _scores = ref.encode(embedder.runtime.params, ids, mask, body)
    # the reference's own tokenizer gives the same ids
    assert [ref.tokenize(t, 512, 64) for t in texts] == [list(r[m > 0]) for r, m in zip(ids, mask)]
    assert np.median(np.linalg.norm(np.stack(vectors) - np.asarray(want), axis=1)) < BF16_TOL


def test_embedder_takes_the_path_of_a_config_file():
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    embedder = SentenceTransformerEmbedder(trunk=CONFIG_FILE)  # parameters are made when first read
    config = embedder.runtime.config
    assert type(embedder.runtime) is TrunkRuntime and embedder.tokenizer.vocab_size == config.vocab_size == 131072
    assert embedder.get_embedding_dimension() == 3584 and len(config.layer_table()) == 6
    with pytest.raises(TypeError):
        SentenceTransformerEmbedder(trunk={"hidden_size": 64})  # a dict is no path: TrunkConfig.from_dict
