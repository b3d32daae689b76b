"""The trunk runtime (``xpacks/llm/_trunk.py``, ``ops/moe.py``) against the
benchmark's plain reference, at the configuration's rehearse sizes on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_trunk as ref
from pathway_tpu.ops import moe, residual_mix
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
    assert set(_trunk.param_shapes(config)["layers"][0]) == {"attn_norm", "attn", "ffn_norm", "ffn"}
    linear = TrunkConfig.from_dict(rehearse_dict(), layer_types=("linear_attention",) * 3)
    with pytest.raises(NotImplementedError, match="'linear_attention'"):
        _trunk.param_shapes(linear)
    with pytest.raises(ValueError, match="scoring_func"):
        TrunkConfig(scoring_func="tanh")
    with pytest.raises(ValueError, match="scoring_func"):  # softmax over the chosen k is the normalised one only
        TrunkConfig(scoring_func="softmax", norm_topk_prob=False)
    with pytest.raises(ValueError, match="use_qk_norm"):
        TrunkConfig(use_qk_norm=True)


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
    got = _trunk._mhc(p, streams, sublayer, config)  # one sub-layer of the kind's layer
    ref_streams = jnp.transpose(streams, (1, 2, 0, 3))
    want = ref.residual(p, ref_streams, sublayer, body)
    assert np.abs(np.asarray(jnp.transpose(got, (1, 2, 0, 3))) - np.asarray(want)).max() < 1e-4
    _mixed_in, packed = _trunk.mhc_coefficients(p, streams, config)  # as the mix-in kernel hands them to mix-out
    _pre, _post, h_res = residual_mix.coefficients(packed, n, h.shape[1])
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


# tokens, top_k, experts, experts held: toy token counts at the trunk cells' expert shapes
DISPATCH_SHAPES = [
    pytest.param(48, 2, 8, None, id="48-2"),
    pytest.param(300, 2, 8, None, id="300-2"),
    pytest.param(200, 3, 8, None, id="200-3"),
    pytest.param(64, 10, 512, (0, 256), id="512-first-half-top10"),
    pytest.param(64, 10, 512, (256, 256), id="512-second-half-top10"),
    pytest.param(96, 10, 72, (36, 36), id="72-second-half-top10"),
    pytest.param(96, 8, 128, (0, 16), id="128-first-16-top8"),
]


def dispatch_inputs(tokens, top_k, experts, held, routing):
    """A routing and its validity, the last 8 positions padding."""
    first = held[0] if held else 0
    rng = np.random.default_rng(9)
    if routing == "one_expert":  # every token's first choice is the same held expert
        hot = max(5, top_k)
        second = rng.integers(0, hot, tokens)
        choice = first + np.stack([np.full(tokens, hot)] + [(second + j) % hot for j in range(top_k - 1)], axis=1)
    else:
        choice = np.stack([rng.permutation(experts)[:top_k] for _ in range(tokens)])
    valid = np.ones(tokens, bool)
    valid[tokens - 8 :] = False  # padding positions are routed nowhere
    return choice, valid


@pytest.mark.parametrize("tokens, top_k, experts, held", DISPATCH_SHAPES)
@pytest.mark.parametrize("routing", ["one_expert", "random"])
def test_dispatch_loses_and_duplicates_no_row(tokens, top_k, experts, held, routing):
    align = moe.TILE_ROWS
    first, count = held or (0, experts)
    choice, valid = dispatch_inputs(tokens, top_k, experts, held, routing)
    plan = moe.dispatch(jnp.asarray(choice, jnp.int32), jnp.asarray(valid), experts, held)
    src, dest = np.asarray(plan.src), np.asarray(plan.dest)
    sizes, counts = np.asarray(plan.group_sizes), np.asarray(plan.counts)
    rows = moe.plan_rows(tokens * top_k, count)
    assert len(src) == rows and len(sizes) == count and (sizes % align == 0).all()
    assert (counts == np.bincount(choice[valid].reshape(-1), minlength=experts)).all()
    mine = counts[first : first + count]  # groups are indexed by the held expert's local id
    assert (sizes >= mine).all() and (sizes - mine < align).all()
    # every valid pair to a held expert has one row of its own, in its expert's group, fed by its token
    starts = np.cumsum(sizes) - sizes
    here = valid[:, None] & (choice >= first) & (choice < first + count)
    pair_rows = dest[here]
    assert len(set(pair_rows)) == here.sum() > 0 and pair_rows.max() < rows
    for t, j in zip(*np.nonzero(here)):
        row, local = dest[t, j], choice[t, j] - first
        assert src[row] == t and starts[local] <= row < starts[local] + mine[local]
    assert (dest[~here] == rows).all()
    assert (src < tokens).sum() == here.sum()  # every other row is padding


def dispatch_by_comparison(choice, valid, n_experts, experts_held=None):
    """``moe.dispatch`` as it was written first: the experts counted by a
    [pairs, n_experts] comparison, each row's group found by a [rows, held]
    one, the sort inverted by a second sort. The reference for the bits."""
    tokens, top_k = choice.shape
    first, held = experts_held or (0, n_experts)
    pairs = tokens * top_k
    rows = moe.plan_rows(pairs, held)
    flat = jnp.where(jnp.repeat(valid, top_k), choice.reshape(-1), -1)
    counts_all = (flat[:, None] == jnp.arange(n_experts)[None, :]).sum(0, dtype=jnp.int32)
    local = flat - first
    key = jnp.where((flat >= 0) & (local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = counts_all[first : first + held]
    sizes = -(-counts // moe.TILE_ROWS) * moe.TILE_ROWS
    starts = jnp.cumsum(sizes) - sizes
    offsets = jnp.cumsum(counts) - counts
    row = jnp.arange(rows, dtype=jnp.int32)
    group = (row[:, None] >= (starts + sizes)[None, :]).sum(1, dtype=jnp.int32)
    inside = jnp.minimum(group, held - 1)
    rank = row - starts[inside]
    live = (group < held) & (rank < counts[inside])
    at = jnp.clip(offsets[inside] + rank, 0, pairs - 1)
    src = jnp.where(live, order[at] // top_k, tokens)
    where = jnp.argsort(order).astype(jnp.int32)
    sorted_key = key[order]
    sorted_inside = jnp.minimum(sorted_key, held - 1)
    sorted_dest = jnp.where(
        sorted_key < held,
        starts[sorted_inside] + jnp.arange(pairs, dtype=jnp.int32) - offsets[sorted_inside],
        rows,
    )
    return moe.Plan(src, sorted_dest[where].reshape(tokens, top_k), sizes, counts_all)


@pytest.mark.parametrize("tokens, top_k, experts, held", DISPATCH_SHAPES)
@pytest.mark.parametrize("routing", ["one_expert", "random"])
def test_dispatch_gives_the_comparisons_plan_bit_for_bit(tokens, top_k, experts, held, routing):
    choice, valid = dispatch_inputs(tokens, top_k, experts, held, routing)
    args = (jnp.asarray(choice, jnp.int32), jnp.asarray(valid), experts, held)
    for name, got, want in zip(moe.Plan._fields, moe.dispatch(*args), dispatch_by_comparison(*args)):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert np.array_equal(np.asarray(got), np.asarray(want)), name


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (loop bodies, branches)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(inner, ClosedJaxpr):
                    yield from equations(inner.jaxpr)
                elif isinstance(inner, Jaxpr):
                    yield from equations(inner)


@pytest.mark.parametrize(
    "tokens, top_k, experts, held",
    [
        pytest.param(16384, 10, 512, (0, 256), id="qwen3-next"),
        pytest.param(16384, 10, 72, (0, 36), id="granite"),
        pytest.param(16384, 8, 128, (0, 16), id="command-a"),
        pytest.param(16384, 4, 64, None, id="xing4"),
    ],
)
def test_no_dispatch_intermediate_grows_with_pairs_times_experts(tokens, top_k, experts, held):
    """At the trunk cells' forward of 16,384 positions: traced, not compiled."""
    traced = jax.make_jaxpr(lambda c, v: moe.dispatch(c, v, experts, held))(
        jax.ShapeDtypeStruct((tokens, top_k), jnp.int32), jax.ShapeDtypeStruct((tokens,), jnp.bool_)
    )
    largest = max(int(np.prod(var.aval.shape)) for eqn in equations(traced.jaxpr) for var in eqn.outvars)
    rows = moe.plan_rows(tokens * top_k, (held or (0, experts))[1])
    assert rows <= largest <= 4 * rows


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
        "trunk": "toy", "residual": "mhc_fused", "expert_rows_useful": 14 * 2 * moe_layers,
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


# -- (g) grouped-query trunks: command-a-plus-05-2026 at its rehearse sizes ----------

from benchmarks.harness import reference_gqa as gqa_ref  # noqa: E402
from pathway_tpu.ops import block_attention  # noqa: E402

GQA_FILE = os.path.join(ROOT, "benchmarks", "configs", "command-a-plus-05-2026.json")


def toy_dict(path: str, **changes) -> dict:
    """A configuration file at its ``rehearse`` sizes (nested groups overlaid key by key)."""
    with open(path, encoding="utf-8") as f:
        body = json.load(f)
    toy = body.pop("rehearse")
    for key, value in toy.items():
        if isinstance(value, dict) and isinstance(body.get(key), dict):
            body[key] = {**body[key], **value}
        else:
            body[key] = value
    body.update(changes)
    return body


def gqa_dict(**changes) -> dict:
    return toy_dict(GQA_FILE, **changes)


@pytest.fixture(scope="module")
def gqa():
    body = gqa_dict()
    return body, TrunkConfig.from_dict(body, name="toy-gqa")


@pytest.fixture(scope="module")
def gqa_runtime(gqa):
    _body, config = gqa
    return TrunkRuntime(config, max_len=128, seed=7, dtype=jnp.float32)


def reference_rows(params, ids, mask, body):
    return np.stack(
        [np.asarray(gqa_ref.encode(params, row, int(m.sum()), body)[0]) for row, m in zip(ids, mask)]
    )


def test_the_config_file_reads_as_the_issue_says():
    config = TrunkConfig.from_file(GQA_FILE, name="command-a-plus-05-2026")
    table = config.layer_table()
    assert [k.attention for k in table] == ["gqa_window"] * 3 + ["gqa_full"]
    assert {k.ffn for k in table} == {"moe"} and {k.residual for k in table} == {"parallel"}
    assert (config.hidden_size, config.num_attention_heads, config.num_key_value_heads, config.head_dim) == (4096, 128, 8, 128)
    assert (config.n_routed_experts, config.held, config.num_experts_per_tok, config.n_shared_experts) == (128, (0, 16), 8, 4)
    assert (config.moe_intermediate_size, config.sliding_window, config.vocab_size) == (4096, 4096, 32768)
    assert config.norm_eps == 1e-5 and config.routed_scaling_factor == 1.0 and config.topk_method == "greedy"
    assert config.shared_expert_combination_strategy == "average"
    shapes = _trunk.param_shapes(config)
    assert "bias" not in shapes["layers"][0]["ffn"] and set(shapes["layers"][0]) == {"norm", "attn", "ffn"}
    sizes = [int(np.prod(s)) for s, _ in jax.tree_util.tree_leaves(shapes, is_leaf=_trunk._is_leaf)]
    assert 4.72e9 < sum(sizes) < 4.74e9  # ISSUE 32's arithmetic: 4,598.9M in the layers + 134.2M of embedding


@pytest.mark.parametrize("rows, seed", [(3, 0), (5, 1), (8, 2)])
def test_gqa_forward_float32_matches_the_reference_past_the_window(gqa, gqa_runtime, rows, seed):
    body, config = gqa
    ids, mask = batch(rows, 128, seed)  # row 0 fills 128 positions: 8 windows of 16
    assert mask.sum(axis=1).max() > 4 * config.sliding_window
    got, info = gqa_runtime.forward(ids, mask)
    want = reference_rows(gqa_runtime.params, ids, mask, body)
    assert np.linalg.norm(got - want, axis=1).max() < F32_TOL
    assert info["attn_pairs_allowed"] == sum(
        block_attention.pairs_allowed(int(t), w) for t in mask.sum(axis=1) for w in (16, 16, 16, None)
    )
    # one block a row at this width, in each of the four layers; the bucket's padding rows visit nothing
    assert info["attn_pairs_visited"] == sum(
        block_attention.pairs_visited(128, w, tokens=int(t)) for t in mask.sum(axis=1) for w in (16, 16, 16, None)
    ) == rows * 4 * 128 * 128 <= info["batch_bucket"] * 4 * 128 * 128


def test_gqa_forward_bfloat16_follows_its_own_experts(gqa):
    body, config = gqa
    runtime = TrunkRuntime(config, max_len=128, seed=8)
    ids, mask = batch(4, 64, 3)
    got, info = runtime.forward(ids, mask, routing=True)
    choice = info["expert_choice"]
    assert choice.shape == (4, 4, 64, 2) and ((choice >= 0).all(axis=-1) == (mask > 0)[None]).all()
    want = np.stack(
        [
            np.asarray(gqa_ref.encode(runtime.params, ids[i], int(mask[i].sum()), body, forced=choice[:, i])[0])
            for i in range(4)
        ]
    )
    assert np.linalg.norm(got - want, axis=1).max() < BF16_TOL


@pytest.fixture(scope="module")
def gqa_blocks(gqa):
    body, config = gqa
    params = f32_tree(_trunk.init_params(config, 13, jnp.float32))
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 48, config.hidden_size), jnp.float32)
    return body, config, params, h


@pytest.mark.parametrize("kind, types", [("gqa_window", "sliding_attention"), ("gqa_full", "full_attention")])
def test_gqa_attention_blocks(gqa_blocks, kind, types):
    body, config, params, h = gqa_blocks
    p = params["layers"][0]["attn"]
    ctx = {"rope_pairs": _trunk.interleaved_rope_tables(config, h.shape[1])}
    got = np.asarray(_trunk.ATTENTION[kind].apply(p, h, config, ctx))
    want = np.stack([np.asarray(gqa_ref.attention(p, row, body, types)) for row in h])
    assert np.abs(got - want).max() < 1e-4
    other = "gqa_full" if kind == "gqa_window" else "gqa_window"
    assert np.abs(got - np.asarray(_trunk.ATTENTION[other].apply(p, h, config, ctx))).max() > 1e-2


def test_a_full_layer_turns_nothing_and_a_window_layer_by_interleaved_pairs(gqa_blocks):
    _body, config, params, h = gqa_blocks
    p = params["layers"][0]["attn"]
    short = h[:, : config.sliding_window]  # inside one window the masks agree: what differs is the rotary
    ctx = {"rope_pairs": _trunk.interleaved_rope_tables(config, short.shape[1])}
    turned = _trunk.ATTENTION["gqa_window"].apply(p, short, config, ctx)
    plain = _trunk.ATTENTION["gqa_full"].apply(p, short, config, ctx)
    assert np.abs(np.asarray(turned) - np.asarray(plain)).max() > 1e-2
    assert np.abs(np.asarray(turned[:, 0]) - np.asarray(plain[:, 0])).max() < 1e-5  # position 0 turns by nothing
    # the program turns de-interleaved halves, the reference interleaved pairs: the same logits
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 24, 16), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 24, 16), jnp.float32)
    cos, sin = _trunk.interleaved_rope_tables(config, 24)
    halves = lambda x: jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)  # noqa: E731
    mine = jnp.einsum("bhqe,bhke->bhqk", _trunk._rotate_pairs(halves(q), cos, sin), _trunk._rotate_pairs(halves(k), cos, sin))
    theirs = jnp.einsum(
        "qhe,khe->hqk",
        gqa_ref.rotate_pairs(jnp.moveaxis(q[0], 0, 1), config.rope_theta),
        gqa_ref.rotate_pairs(jnp.moveaxis(k[0], 0, 1), config.rope_theta),
    )
    assert np.abs(np.asarray(mine[0]) - np.asarray(theirs)).max() < 1e-4
    pair = gqa_ref.rotate_pairs(jnp.ones((3, 1, 4)), 10.0)  # dims (0, 1) turn by t, (2, 3) by t / sqrt(10)
    assert np.allclose(pair[2, 0], [np.cos(2) - np.sin(2), np.cos(2) + np.sin(2)] + [np.cos(2 / 10**0.5) - np.sin(2 / 10**0.5), np.cos(2 / 10**0.5) + np.sin(2 / 10**0.5)], atol=1e-5)


def test_query_head_j_reads_key_value_head_j_over_group(gqa_blocks):
    _body, config, params, h = gqa_blocks
    p = dict(params["layers"][0]["attn"])
    heads, width = config.num_attention_heads, config.head_dim
    group = heads // config.num_key_value_heads
    p["wo"] = jnp.eye(heads * width).reshape(heads, width, heads * width)  # the heads' outputs side by side
    base = np.asarray(_trunk.ATTENTION["gqa_full"].apply(p, h, config, {})).reshape(2, 48, heads, width)
    p["wv"] = p["wv"].at[:, 1].multiply(2.0)  # key-value head 1 alone
    moved = np.asarray(_trunk.ATTENTION["gqa_full"].apply(p, h, config, {})).reshape(2, 48, heads, width)
    changed = np.abs(moved - base).max(axis=(0, 1, 3)) > 1e-6
    assert changed.tolist() == [group <= j < 2 * group for j in range(heads)]


@pytest.mark.parametrize("window", [1, 5, 16])
def test_the_window_counts_the_token_itself(window):
    # a value only position s carries reaches t while t - s <= window - 1 and not at t - s = window
    length, s = 40, 7
    q = jnp.zeros((1, 1, 2, length, 8))  # flat logits: every allowed key weighs the same
    k = jnp.zeros((1, 1, length, 8))
    v = jnp.zeros((1, 1, length, 8)).at[0, 0, s].set(1.0)
    out = np.asarray(block_attention.attention(q, k, v, scale=1.0, window=window, block_q=8, block_k=16))[0, 0, 0, :, 0]
    seen = out > 0
    assert seen.tolist() == [s <= t <= s + window - 1 for t in range(length)]
    assert np.allclose(out[seen], 1.0 / np.minimum(np.arange(length)[seen] + 1, window))


def materialised(q, k, v, scale, window):
    length = q.shape[3]
    logits = jnp.einsum("bhgtd,bhsd->bhgts", q, k, precision="highest") * scale
    t, s = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    allowed = (s <= t) if window is None else (s <= t) & (t - s < window)
    return jnp.einsum("bhgts,bhsd->bhgtd", jax.nn.softmax(jnp.where(allowed, logits, -jnp.inf), axis=-1), v, precision="highest")


@pytest.mark.parametrize(
    "length, window, block_q, block_k",
    [(64, None, 16, 32), (64, 16, 16, 32), (128, 16, 16, 16), (128, 40, 32, 64), (100, 16, None, None),
     (160, 48, 32, 64), (32, 4096, None, None), (96, 33, 32, 16)],
)
def test_blocked_attention_is_materialised_attention(length, window, block_q, block_k):
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    q = 2.0 * jax.random.normal(keys[0], (2, 2, 4, length, 16))
    k, v = jax.random.normal(keys[1], (2, 2, length, 16)), jax.random.normal(keys[2], (2, 2, length, 16))
    got = block_attention.attention(q, k, v, scale=0.25, window=window, block_q=block_q, block_k=block_k)
    assert np.abs(np.asarray(got) - np.asarray(materialised(q, k, v, 0.25, window))).max() < 2e-5
    # the blocks visited hold every allowed pair and, with a window, not every causal block
    size_q, size_k = block_attention.blocks(length, block_q, block_k)
    steps = block_attention.visited_steps(length, window, size_q, size_k)
    assert block_attention.pairs_visited(length, window, block_q, block_k) >= block_attention.pairs_allowed(length, window)
    if window is not None and window + size_q + size_k < length:
        assert sum(steps) < sum(block_attention.visited_steps(length, None, size_q, size_k))


RAGGED = [  # length, window, block_q, block_k, real tokens a row: none, inside a block, on a block's edge, all
    (64, None, 16, 32, (0, 21, 32, 64)),
    (64, 16, 16, 32, (0, 21, 32, 64)),
    (128, 40, 32, 64, (0, 33, 96, 128)),
    (100, 16, None, None, (0, 1, 57, 100)),  # one block a row: a row is live or dead as a whole
    (96, 33, 32, 16, (0, 5, 64, 96)),
    (160, None, 32, 64, (0, 100, 128, 160)),
]


def ragged_inputs(length, rows):
    keys = jax.random.split(jax.random.PRNGKey(length + 1), 3)
    q = 2.0 * jax.random.normal(keys[0], (rows, 2, 4, length, 16))
    return q, jax.random.normal(keys[1], (rows, 2, length, 16)), jax.random.normal(keys[2], (rows, 2, length, 16))


@pytest.mark.parametrize("length, window, block_q, block_k, tokens", RAGGED)
def test_ragged_lengths_keep_every_real_position_and_zero_every_dead_block(length, window, block_q, block_k, tokens):
    q, k, v = ragged_inputs(length, len(tokens))
    kw = dict(scale=0.25, window=window, block_q=block_q, block_k=block_k)
    got = np.asarray(block_attention.attention(q, k, v, lengths=jnp.asarray(tokens, jnp.int32), **kw))
    full = np.asarray(block_attention.attention(q, k, v, **kw))
    want = np.asarray(materialised(q, k, v, 0.25, window))
    size_q, _ = block_attention.blocks(length, block_q, block_k)
    for row, t in enumerate(tokens):
        live_until = -(-t // size_q) * size_q  # the last live block's padding positions are computed as before
        assert np.abs(got[row, :, :, :t] - want[row, :, :, :t]).max(initial=0.0) < 2e-5
        assert np.array_equal(got[row, :, :, :live_until], full[row, :, :, :live_until])  # to the last bit
        assert not got[row, :, :, live_until:].any()
    assert np.isfinite(got).all()


@pytest.mark.parametrize("length, window, block_q, block_k, tokens", RAGGED)
def test_no_lengths_is_every_row_full(length, window, block_q, block_k, tokens):
    q, k, v = ragged_inputs(length, 2)
    kw = dict(scale=0.25, window=window, block_q=block_q, block_k=block_k)
    full = block_attention.attention(q, k, v, lengths=jnp.full((2,), length, jnp.int32), **kw)
    assert np.array_equal(np.asarray(block_attention.attention(q, k, v, **kw)), np.asarray(full))


@pytest.mark.parametrize("length, window, block_q, block_k, tokens", RAGGED)
def test_pairs_visited_counts_the_steps_the_kernel_runs(length, window, block_q, block_k, tokens):
    size_q, size_k = block_attention.blocks(length, block_q, block_k)
    padded = length + -length % max(size_q, size_k)
    grid_steps = max(block_attention.visited_steps(padded, window, size_q, size_k))
    for t in tokens:
        ran = 0  # the kernel's predicate, grid step by grid step: live & (lo + j <= hi)
        for qi in range(padded // size_q):
            lo, hi = block_attention.kv_range(qi, size_q, size_k, window)
            ran += sum(qi * size_q < t and lo + j <= hi for j in range(grid_steps))
        assert block_attention.pairs_visited(length, window, block_q, block_k, tokens=t) == ran * size_q * size_k
        assert block_attention.pairs_visited(length, window, block_q, block_k, tokens=t) >= block_attention.pairs_allowed(t, window)
    assert block_attention.pairs_visited(length, window, block_q, block_k, tokens=length) == block_attention.pairs_visited(
        length, window, block_q, block_k
    )
    assert block_attention.visited_steps(length, window, size_q, size_k, tokens=0) == [0] * -(-length // size_q)


def test_a_dead_block_reads_no_key_and_no_query():
    # what lies past a row's last live block is never touched: poison there reaches no output
    length, tokens = 128, (0, 40, 64, 128)
    q, k, v = ragged_inputs(length, len(tokens))
    clean = np.asarray(block_attention.attention(q, k, v, scale=0.25, lengths=jnp.asarray(tokens), block_q=16, block_k=32))
    for row, t in enumerate(tokens):
        dead_from = -(-t // 16) * 16
        q = q.at[row, :, :, dead_from:].set(jnp.nan)
        k, v = (a.at[row, :, -(-dead_from // 32) * 32 :].set(jnp.nan) for a in (k, v))
    got = np.asarray(block_attention.attention(q, k, v, scale=0.25, lengths=jnp.asarray(tokens), block_q=16, block_k=32))
    assert np.array_equal(got, clean)


def test_a_window_layer_visits_under_half_of_the_causal_blocks_at_16k():
    whole = block_attention.pairs_visited(16384, None)
    assert 0.40 < block_attention.pairs_visited(16384, 4096) / whole < 0.50
    assert block_attention.pairs_allowed(16384, 4096) == 4096 * 4097 // 2 + (16384 - 4096) * 4096


def test_the_parallel_residual_gives_both_blocks_one_normed_input(gqa_blocks):
    _body, config, params, h = gqa_blocks
    p = {"norm": params["layers"][0]["norm"], "attn": "a", "ffn": "f"}
    seen = {}

    def attend(params_of, u):
        seen[params_of] = u
        return 2.0 * u

    def feed(params_of, u):
        seen[params_of] = u
        return jnp.tanh(u)

    got = _trunk.RESIDUAL["parallel"].layer(p, h, attend, feed, config)
    normed = _trunk.layer_norm(h, p["norm"], config.norm_eps)
    assert seen["a"] is seen["f"] and np.abs(np.asarray(seen["a"]) - np.asarray(normed)).max() == 0
    assert np.abs(np.asarray(got) - np.asarray(h + 2.0 * normed + jnp.tanh(normed))).max() < 1e-6
    want = gqa_ref.layer_norm(h, p["norm"], config.norm_eps)
    assert np.abs(np.asarray(normed) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(normed).mean(axis=-1)).max() < 0.05  # mean-centred, up to the gain's spread


def test_shared_experts_are_averaged_and_the_chosen_weights_sum_to_one(gqa_blocks):
    body, config, params, h = gqa_blocks
    p = params["layers"][1]["ffn"]
    flat = h.reshape(-1, h.shape[-1])
    ctx = {"valid": jnp.ones(flat.shape[0], bool), "expert_counts": [], "expert_choice": []}
    got = np.asarray(_trunk.FFN["moe"].apply(p, h, config, ctx)).reshape(flat.shape)
    want, scores = gqa_ref.expert_ffn(p, flat, body, experts_held=(0, 4))
    assert np.abs(got - np.asarray(want)).max() < 1e-4
    # the shared part is the mean of the experts that lie side by side in the one FFN
    f, n = config.moe_intermediate_size, config.n_shared_experts
    singles = [
        _trunk._gated_ffn(
            {"w_gate": p["shared"]["w_gate"][:, i * f : (i + 1) * f], "w_up": p["shared"]["w_up"][:, i * f : (i + 1) * f],
             "w_down": p["shared"]["w_down"][i * f : (i + 1) * f]}, flat)
        for i in range(n)
    ]
    routed, _counts, choice = moe.expert_layer(
        flat, ctx["valid"], p["router"], None, p["w_gate"], p["w_up"], p["w_down"],
        top_k=2, scale=1.0, experts_held=(0, 4),
    )
    assert np.abs(got - np.asarray(routed + sum(singles) / n)).max() < 1e-4
    weights, chosen = moe.route(flat, p["router"], None, top_k=2, scale=1.0)
    assert np.abs(np.asarray(weights).sum(axis=1) - 1).max() < 1e-6
    own = np.argsort(np.asarray(scores), axis=-1)[:, -2:]
    assert (np.sort(np.asarray(chosen), axis=-1) == np.sort(own, axis=-1)).all() and (np.asarray(choice) == np.asarray(chosen)).all()


def test_all_the_shares_of_a_layer_add_up_to_the_uncut_layer(gqa):
    """Eight experts over shares of 2: what the four shares' routed parts
    give, with the attention, the shared experts and x counted once, is the
    uncut layer as the reference computes it."""
    body, _config = gqa
    whole_body = gqa_dict(num_experts=8, experts_held=None)
    whole = TrunkConfig.from_dict(whole_body, name="uncut")
    assert whole.held == (0, 8) and whole.n_routed_experts == 8
    params = f32_tree(_trunk.init_params(whole, 17, jnp.float32))
    p = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, whole.hidden_size), jnp.float32)
    h = _trunk.layer_norm(x, p["norm"], whole.norm_eps)
    flat = h.reshape(-1, h.shape[-1])
    valid = jnp.ones(flat.shape[0], bool)
    parts = []
    for first in range(0, 8, 2):
        routed, _counts, _choice = moe.expert_layer(
            flat, valid, p["ffn"]["router"], None, p["ffn"]["w_gate"][first : first + 2],
            p["ffn"]["w_up"][first : first + 2], p["ffn"]["w_down"][first : first + 2],
            top_k=2, scale=1.0, experts_held=(first, 2),
        )
        parts.append(np.asarray(routed))
    assert all(np.abs(part).max() > 0 for part in parts)
    ctx = {"rope_pairs": _trunk.interleaved_rope_tables(whole, 40)}
    attention = np.asarray(_trunk.ATTENTION["gqa_window"].apply(p["attn"], h, whole, ctx))[0]
    shared = np.asarray(_trunk._gated_ffn(p["ffn"]["shared"], flat)) / whole.n_shared_experts
    want, _scores = gqa_ref.layer(p, x[0], None, whole_body, "sliding_attention")
    assert np.abs(np.asarray(x[0]) + attention + shared + sum(parts) - np.asarray(want)).max() < 2e-4
    # and a cut layer is the program's own layer on its share
    cut = TrunkConfig.from_dict(gqa_dict(num_experts=2, experts_held=[2, 2]), name="cut")
    held = dict(p, ffn={k: (v[2:4] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p["ffn"].items()})
    ctx.update(valid=valid, expert_counts=[], expert_choice=[])

    def attend(p_attn, u):
        return _trunk.ATTENTION["gqa_window"].apply(p_attn, u, cut, ctx)

    def feed(p_ffn, u):
        return _trunk.FFN["moe"].apply(p_ffn, u, cut, ctx)

    got = np.asarray(_trunk.RESIDUAL["parallel"].layer(held, x, attend, feed, cut))[0]
    assert np.abs(got - (np.asarray(x[0]) + attention + shared + parts[1])).max() < 2e-4


def test_gqa_padding_and_companions_change_no_vector(gqa_runtime):
    ids, mask = batch(rows=3, width=64, seed=5)
    together = gqa_runtime.forward_ids(ids, mask)
    for i in range(3):
        alone = gqa_runtime.forward_ids(ids[i : i + 1], mask[i : i + 1])
        assert np.abs(alone[0] - together[i]).max() < 1e-5
    wide, info = gqa_runtime.forward(np.pad(ids, ((0, 0), (0, 64))), np.pad(mask, ((0, 0), (0, 64))))
    assert info["len_bucket"] == 128 and np.abs(wide - together).max() < 1e-5


# -- (h) the grouped matmul's column blocks and the expert layer's passes --------------


@pytest.mark.parametrize(
    "k, n, block",
    [(3584, 1024, 1024), (1024, 3584, 3584), (4096, 4096, 1024), (64, 32, 32)],
    ids=["xing4-up", "xing4-down", "command-a", "toy"],
)
def test_grouped_matmul_in_column_blocks_is_ragged_dot(k, n, block):
    assert moe.column_block(k, n, 2) == block
    groups, sizes = 3, np.array([128, 0, 256])
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.standard_normal((512, k)), jnp.bfloat16)  # the last tile is past every group
    w = jnp.asarray(rng.standard_normal((groups, k, n)) / np.sqrt(k), jnp.bfloat16)
    tile_group, used = moe.tile_groups(jnp.asarray(sizes, jnp.int32), 4)
    assert np.asarray(tile_group).tolist() == [0, 2, 2, 2] and int(used[0]) == 3
    got = np.asarray(moe.grouped_matmul(x, w, tile_group, used), np.float32)[:384]
    want = np.asarray(jax.lax.ragged_dot(x[:384], w, jnp.asarray(sizes, jnp.int32)), np.float32)
    assert np.abs(got - want).max() < 0.05 and np.abs(want).max() > 1


@pytest.mark.parametrize("routing", ["all_to_the_held", "spread"])
def test_an_expert_layer_in_passes_drops_no_pair(routing):
    """2 of 16 experts held: a pass has room for a quarter of the pairs;
    with every token sent to the held experts it takes four passes and more."""
    tokens, d, f, experts, top_k = 600, 32, 16, 16, 2
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    router = rng.standard_normal((d, experts)).astype(np.float32) * 0.1
    if routing == "all_to_the_held":
        router[:, 4:6] += 4.0 * np.sign(router[:, 4:6])  # |logit| large ...
        h = jnp.abs(h) * jnp.sign(jnp.asarray(router[:, 4]))[None, :]  # ... and positive for experts 4 and 5
        router[:, 5] = router[:, 4] * 0.9
    w = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[1]), jnp.float32)  # noqa: E731
    w_gate, w_up, w_down = w(2, d, f), w(2, d, f), w(2, f, d)
    valid = jnp.asarray(np.arange(tokens) < tokens - 9)
    assert moe.pass_rows(tokens * top_k, experts, 2) < moe.plan_rows(tokens * top_k, 2)
    got, counts, choice = moe.expert_layer(
        h, valid, jnp.asarray(router), None, w_gate, w_up, w_down, top_k=top_k, scale=1.0, experts_held=(4, 2)
    )
    if routing == "all_to_the_held":
        assert int(np.asarray(counts)[4:6].sum()) == 2 * (tokens - 9)
    body = {"num_experts_per_tok": top_k, "norm_topk_prob": True}
    p = {"router": jnp.asarray(router), "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    forced = jnp.asarray(choice)
    want, _scores = gqa_ref.expert_ffn(p, h, body, experts_held=(4, 2), shared=False, forced=forced)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    assert np.abs(np.asarray(got)[tokens - 9 :]).max() == 0
    busiest = int(np.asarray(counts)[4:6].max())
    sparse, _scores = gqa_ref.expert_ffn(p, h, body, experts_held=(4, 2), shared=False, forced=forced, busiest=max(busiest, 1))
    assert np.abs(np.asarray(sparse) - np.asarray(want)).max() < 1e-4


# -- (i) granite-4.0-h-small: Mamba-2 layers, an unrotated full layer, a softmax-of-top-k router ----

from benchmarks.harness import reference_ssm as ssm_ref  # noqa: E402
from pathway_tpu.ops import ssd_scan  # noqa: E402

SSM_FILE = os.path.join(ROOT, "benchmarks", "configs", "granite-4.0-h-small.json")


def ssm_dict(**changes) -> dict:
    return toy_dict(SSM_FILE, **changes)


@pytest.fixture(scope="module")
def ssm():
    body = ssm_dict()
    return body, TrunkConfig.from_dict(body, name="toy-ssm")


@pytest.fixture(scope="module")
def ssm_runtime(ssm):
    _body, config = ssm
    runtime = TrunkRuntime(config, max_len=128, seed=7, dtype=jnp.float32)
    # the stream enters at unit scale (as the benchmark's weights have it): what a layer adds is then visible
    runtime.params = dict(runtime.params, embed=runtime.params["embed"] / config.embedding_multiplier)
    return runtime


def ssm_reference_rows(params, ids, mask, body, **kw):
    return np.stack(
        [np.asarray(ssm_ref.encode(params, row, int(m.sum()), body, **kw)[0]) for row, m in zip(ids, mask)]
    )


def test_the_granite_config_file_reads_as_the_issue_says():
    config = TrunkConfig.from_file(SSM_FILE, name="granite-4.0-h-small")
    table = config.layer_table()
    assert [k.attention for k in table] == ["mamba2"] * 5 + ["gqa_full"] + ["mamba2"] * 4
    assert {k.ffn for k in table} == {"moe"} and {k.residual for k in table} == {"add"}
    assert (config.hidden_size, config.num_attention_heads, config.num_key_value_heads, config.head_dim) == (4096, 32, 8, 128)
    assert (config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state, config.mamba_d_conv, config.mamba_chunk_size) == (128, 64, 128, 4, 256)
    assert (config.n_routed_experts, config.held, config.num_experts_per_tok, config.n_shared_experts) == (72, (0, 36), 10, 1)
    assert (config.moe_intermediate_size, config.shared_intermediate_size, config.vocab_size) == (768, 1536, 50176)
    assert (config.attention_multiplier, config.embedding_multiplier, config.residual_multiplier) == (0.0078125, 12, 0.22)
    assert config.scoring_func == "softmax" and config.topk_method == "greedy" and config.routed_scaling_factor == 1.0
    assert config.norm_eps == 1e-5 and config.position_embedding_type == "nope"
    shapes = _trunk.param_shapes(config)
    assert "bias" not in shapes["layers"][0]["ffn"] and shapes["layers"][0]["ffn"]["router"][0] == (4096, 72)
    assert shapes["layers"][0]["attn"]["w_in"][0] == (4096, 16768) and shapes["layers"][0]["attn"]["conv"][0] == (4, 8448)
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(template)) == 4_757_211_776
    mamba = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(template["layers"][0]["attn"]))
    assert mamba == 102_286_976


@pytest.mark.parametrize(
    "key, value",
    [("mamba_n_groups", 8), ("mamba_proj_bias", True), ("normalization_function", "layernorm"), ("position_embedding_type", "rope")],
)
def test_granite_keys_without_a_block_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        TrunkConfig.from_dict(ssm_dict(**{key: value}), name="toy-ssm")


@pytest.mark.parametrize("rows, seed", [(3, 0), (5, 1), (8, 2)])
def test_ssm_forward_float32_matches_the_recurrence_over_several_chunks(ssm, ssm_runtime, rows, seed):
    body, config = ssm
    ids, mask = batch(rows, 128, seed)  # row 0 fills 128 positions: 8 chunks of 16
    assert mask.sum(axis=1).max() == 8 * config.mamba_chunk_size
    got, info = ssm_runtime.forward(ids, mask)
    want = ssm_reference_rows(ssm_runtime.params, ids, mask, body)
    assert np.linalg.norm(got - want, axis=1).max() < F32_TOL
    assert info["ssm_chunks_useful"] == 9 * sum(-(-int(t) // 16) for t in mask.sum(axis=1))
    assert info["ssm_chunks_visited"] == 9 * info["batch_bucket"] * 8
    assert info["attn_pairs_allowed"] == sum(block_attention.pairs_allowed(int(t), None) for t in mask.sum(axis=1))
    # a reference that loses the state between chunks is somebody else's vectors
    lost = ssm_reference_rows(ssm_runtime.params, ids[:2], mask[:2], body, mode="no_carry")
    assert np.linalg.norm(lost - want[:2], axis=1).min() > 50 * F32_TOL


def test_ssm_forward_bfloat16_follows_its_own_experts(ssm):
    body, config = ssm
    runtime = TrunkRuntime(config, max_len=128, seed=8)
    ids, mask = batch(4, 64, 3)
    got, info = runtime.forward(ids, mask, routing=True)
    choice = info["expert_choice"]
    assert choice.shape == (10, 4, 64, 3) and ((choice >= 0).all(axis=-1) == (mask > 0)[None]).all()
    want = np.stack(
        [
            np.asarray(ssm_ref.encode(runtime.params, ids[i], int(mask[i].sum()), body, forced=choice[:, i])[0])
            for i in range(4)
        ]
    )
    assert np.linalg.norm(got - want, axis=1).max() < BF16_TOL


@pytest.fixture(scope="module")
def ssm_blocks(ssm):
    body, config = ssm
    params = f32_tree(_trunk.init_params(config, 13, jnp.float32))
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 72, config.hidden_size), jnp.float32)
    return body, config, params, h


def test_mamba2_block(ssm_blocks):
    body, config, params, h = ssm_blocks
    p = params["layers"][0]["attn"]
    got = _trunk.ATTENTION["mamba2"].apply(p, h, config, {})
    for i in range(2):  # 72 positions: four whole chunks of 16 and a part of one
        want = ssm_ref.mamba(p, h[i], body)
        assert np.abs(np.asarray(got[i]) - np.asarray(want)).max() < 2e-4
    # the convolution sees the token itself and the three before it
    taps = jnp.asarray([[0.0], [0.0], [0.0], [1.0]])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 1))
    assert np.allclose(_trunk.causal_conv(x, taps, jnp.zeros(1)), x)
    assert np.allclose(_trunk.causal_conv(x, taps[::-1], jnp.zeros(1))[:, 3:], x[:, :-3])


def recurrence(x, dt, A, B, C, D):
    """The scan position by position: S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t + D x_t."""

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * A)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t) + D[:, None] * x_t

    def one_row(x, dt, B, C):
        return jax.lax.scan(step, jnp.zeros(x.shape[1:] + B.shape[-1:]), (x, dt, B, C))[1]

    return jax.vmap(one_row)(x, dt, B, C)


def scan_inputs(batch_rows, length, heads, width, states, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch_rows, length, heads, width)), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(batch_rows, length, heads))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, size=heads), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(batch_rows, length, states)), jnp.float32) for _ in range(2))
    return x, dt, A, B, C, jnp.asarray(rng.normal(size=heads), jnp.float32)


@pytest.mark.parametrize(
    "length, chunk, heads, width",
    # chunks that divide the length and that do not; the state carried over 4 to 8 chunks; two blocks of 16
    # heads and one of fewer; 8 heads of 16 share a 128-lane tile, 2 heads of 64 as at the published sizes,
    # a head of 128 has its own
    [(64, 16, 32, 16), (70, 16, 8, 16), (96, 256, 4, 8), (128, 32, 32, 64), (80, 16, 2, 128)],
)
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_ssd_scan_is_the_recurrence(path, length, chunk, heads, width):
    inputs = scan_inputs(2, length, heads, width, 16, seed=length)
    want = np.asarray(recurrence(*inputs))
    if path == "kernel":
        got = ssd_scan.scan_pallas(*inputs, chunk=chunk)  # interpreted: the backend is the CPU
    else:
        got = ssd_scan.scan_xla(*inputs, chunk=chunk)
    assert got.shape == want.shape and np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    if chunk < length:  # a scan that drops the carried state is another function
        x, dt, A, B, C, D = inputs
        lost = jnp.concatenate(  # every chunk scanned from a zero state
            [ssd_scan.scan_xla(x[:, i : i + chunk], dt[:, i : i + chunk], A, B[:, i : i + chunk], C[:, i : i + chunk], D, chunk) for i in range(0, length, chunk)],
            axis=1,
        )
        assert np.abs(np.asarray(lost) - want).max() > 0.05 * np.abs(want).max()
        assert np.abs(np.asarray(lost)[:, :chunk] - want[:, :chunk]).max() < 1e-4 * np.abs(want).max()


def test_scan_chunk_counts():
    assert ssd_scan.chunks_useful([1, 256, 257, 5000]) == 1 + 1 + 2 + 20
    assert ssd_scan.chunks_visited(2, 8192) == 64 and ssd_scan.chunks_visited(8, 64) == 8
    assert ssd_scan.chunks_useful([70], chunk=16) == 5 and ssd_scan.chunks_visited(3, 128, chunk=16) == 24


def test_the_softmax_of_the_top_k_sums_to_one_and_follows_the_logits(ssm_blocks):
    _body, config, params, h = ssm_blocks
    p = params["layers"][0]["ffn"]
    flat = h.reshape(-1, h.shape[-1])
    weights, choice = moe.route(flat, p["router"], None, top_k=3, scale=1.0, scoring="softmax")
    logits = np.asarray(flat @ p["router"])
    assert np.abs(np.asarray(weights).sum(axis=1) - 1).max() < 1e-6
    assert (np.sort(np.asarray(choice), axis=1) == np.sort(np.argsort(logits, axis=1)[:, -3:], axis=1)).all()
    picked = np.take_along_axis(logits, np.asarray(choice), axis=1)
    assert (np.diff(picked, axis=1) <= 0).all() and (np.diff(np.asarray(weights), axis=1) <= 0).all()
    assert np.allclose(np.asarray(weights), np.exp(picked) / np.exp(picked).sum(axis=1, keepdims=True), atol=1e-6)
    sigmoid, _ = moe.route(flat, p["router"], None, top_k=3, scale=1.0)
    assert np.abs(np.asarray(sigmoid) - np.asarray(weights)).max() > 1e-3


@pytest.mark.parametrize("key, other", [("attention_multiplier", 0.25), ("embedding_multiplier", 3.0), ("residual_multiplier", 0.5)])
def test_each_multiplier_changes_the_result(ssm, ssm_runtime, key, other):
    body, _config = ssm
    changed = TrunkConfig.from_dict(dict(body, **{key: other}), name="toy-ssm")
    ids, mask = batch(2, 64, 4)
    runtime = TrunkRuntime(changed, max_len=128, dtype=jnp.float32)
    runtime.params = ssm_runtime.params
    got = runtime.forward_ids(ids, mask)
    assert np.linalg.norm(got - ssm_runtime.forward_ids(ids, mask), axis=1).min() > 1e-3
    want = ssm_reference_rows(runtime.params, ids, mask, dict(body, **{key: other}))
    assert np.linalg.norm(got - want, axis=1).max() < F32_TOL


def test_ssm_padding_and_companions_change_no_vector(ssm_runtime):
    ids, mask = batch(rows=3, width=64, seed=5)
    together = ssm_runtime.forward_ids(ids, mask)
    for i in range(3):
        alone = ssm_runtime.forward_ids(ids[i : i + 1], mask[i : i + 1])
        assert np.abs(alone[0] - together[i]).max() < 1e-5
    wide, info = ssm_runtime.forward(np.pad(ids, ((0, 0), (0, 64))), np.pad(mask, ((0, 0), (0, 64))))
    assert info["len_bucket"] == 128 and np.abs(wide - together).max() < 1e-5


@pytest.mark.parametrize("layer, kind", [(0, "mamba"), (5, "attention")])
def test_the_two_shares_of_a_granite_layer_add_up_to_the_uncut_layer(ssm, layer, kind):
    """Eight experts over two shares of 4 (the deployment's: experts 0-35 and
    36-71 of 72): what the shares' routed parts give, with the mixer, the
    shared expert and x counted once, is the uncut layer as the reference
    computes it."""
    whole_body = ssm_dict(num_local_experts=8, experts_held=None, published={})
    whole = TrunkConfig.from_dict(whole_body, name="uncut")
    assert whole.held == (0, 8) and whole.n_routed_experts == 8
    params = f32_tree(_trunk.init_params(whole, 17, jnp.float32))
    p, m = params["layers"][layer], whole.residual_multiplier
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, whole.hidden_size), jnp.float32)
    block = _trunk.ATTENTION[whole.layer_table()[layer].attention]
    mixed = np.asarray(block.apply(p["attn"], _trunk.rms_norm(x, p["attn_norm"], whole.norm_eps), whole, {}))[0]
    after = x + m * mixed[None]
    flat = _trunk.rms_norm(after, p["ffn_norm"], whole.norm_eps).reshape(-1, whole.hidden_size)
    valid = jnp.ones(flat.shape[0], bool)
    parts = []
    for first in (0, 4):
        routed, _counts, _choice = moe.expert_layer(
            flat, valid, p["ffn"]["router"], None, p["ffn"]["w_gate"][first : first + 4],
            p["ffn"]["w_up"][first : first + 4], p["ffn"]["w_down"][first : first + 4],
            top_k=3, scale=1.0, experts_held=(first, 4), scoring="softmax",
        )
        parts.append(np.asarray(routed))
    assert all(np.abs(part).max() > 0 for part in parts)
    shared = np.asarray(_trunk._gated_ffn(p["ffn"]["shared"], flat))
    want, _logits = ssm_ref.layer(p, x[0], None, whole_body, kind)
    assert np.abs(np.asarray(after[0]) + m * (shared + sum(parts)) - np.asarray(want)).max() < 2e-4
    # and a cut layer is the program's own layer on its share
    cut = TrunkConfig.from_dict(ssm_dict(experts_held=[4, 4]), name="cut")
    held = dict(p, ffn={k: (v[4:8] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p["ffn"].items()})
    ctx = {"valid": valid, "expert_counts": [], "expert_choice": []}

    def attend(p_attn, u):
        return block.apply(p_attn, u, cut, ctx)

    def feed(p_ffn, u):
        return _trunk.FFN["moe"].apply(p_ffn, u, cut, ctx)

    got = np.asarray(_trunk.RESIDUAL["add"].layer(held, x, attend, feed, cut))[0]
    assert np.abs(got - (np.asarray(after[0]) + m * (shared + parts[1]))).max() < 2e-4


@pytest.mark.parametrize(
    "file, leaves, total, signature, program",
    [
        (CONFIG_FILE, 147, 4_323_079_812, "1a103e61132f4735", "bc8af1fcd481872d"),
        (GQA_FILE, 50, 4_733_292_544, "fc28f37aca32afce", "4980178b532d60b4"),
        (SSM_FILE, 168, 4_757_211_776, "34a1ce4295c7079c", "e3c685766aee49ff"),
        (os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.json"), 69, 3_522_030_656, "7c8e5eab530dfeb4", "f4335ab96b8432a5"),
    ],
)
def test_the_other_trunks_parameter_trees_are_unchanged(file, leaves, total, signature, program):
    """xing4's, command-a's and granite's trees, leaf for leaf (path, shape,
    dtype), as the commit before the Mamba kind built them (granite's: as
    the commit before the delta-rule kind), and their forwards at the toy
    sizes, equation for equation (the jaxpr), as the commit before the
    delta-rule kind traced them with the dispatch of one sort and no
    comparison by group (the same plan: section (e)); qwen3-next's tree and
    forward as the commit before the window-with-sinks kinds built and
    traced them."""
    import hashlib

    config = TrunkConfig.from_file(file, name="as-before")
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    flat = jax.tree_util.tree_flatten_with_path(template)[0]
    assert (len(flat), sum(int(np.prod(a.shape)) for _, a in flat)) == (leaves, total)
    described = repr([(str(path), a.shape, str(a.dtype)) for path, a in flat])
    assert hashlib.sha256(described.encode()).hexdigest()[:16] == signature
    if file in (CONFIG_FILE, GQA_FILE):
        assert config.attention_multiplier is None and config.residual_multiplier == 1 and config.embedding_multiplier == 1
        assert config.shared_intermediate_size == 0 and config.scoring_func == "sigmoid"
    toy = TrunkConfig.from_dict(toy_dict(file), name="toy")
    params = jax.eval_shape(lambda: _trunk.init_params(toy, 0, jnp.float32))
    ids, mask = jax.ShapeDtypeStruct((2, 64), jnp.int32), jax.ShapeDtypeStruct((2, 64), jnp.float32)
    traced = str(jax.make_jaxpr(lambda p, i, m: _trunk.forward(p, i, m, config=toy))(params, ids, mask))
    assert hashlib.sha256(traced.encode()).hexdigest()[:16] == program


# -- (j) Qwen3-Next-80B-A3B: gated delta rule layers, an output-gated full layer, a gated shared expert ----

from benchmarks.harness import reference_gdn as gdn_ref  # noqa: E402
from pathway_tpu.ops import gated_delta  # noqa: E402

GDN_FILE = os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.json")


def gdn_dict(**changes) -> dict:
    return toy_dict(GDN_FILE, **changes)


def gdn_params(config, seed, dtype=jnp.float32):
    """The benchmark's weights (decays that remember across chunks, q/k norms near 2)."""
    from benchmarks.harness import weights_gdn

    return weights_gdn.make_params(jax.eval_shape(lambda: _trunk.init_params(config, 0, dtype)), seed)


@pytest.fixture(scope="module")
def gdn():
    body = gdn_dict()
    return body, TrunkConfig.from_dict(body, name="toy-gdn")


@pytest.fixture(scope="module")
def gdn_runtime(gdn):
    _body, config = gdn
    runtime = TrunkRuntime(config, max_len=128, seed=7, dtype=jnp.float32)
    runtime.params = gdn_params(config, 7)
    return runtime


def gdn_reference_rows(params, ids, mask, body, **kw):
    return np.stack(
        [np.asarray(gdn_ref.encode(params, row, int(m.sum()), body, **kw)[0]) for row, m in zip(ids, mask)]
    )


def test_the_qwen3_next_config_file_reads_as_the_issue_says():
    config = TrunkConfig.from_file(GDN_FILE, name="qwen3-next-80b-a3b")
    table = config.layer_table()
    assert [k.attention for k in table] == ["gated_deltanet"] * 3 + ["gqa_gated"]
    assert {k.ffn for k in table} == {"moe"} and {k.residual for k in table} == {"add"} and config.norm_kind == "rms_offset"
    assert (config.hidden_size, config.num_attention_heads, config.num_key_value_heads, config.head_dim) == (2048, 16, 2, 256)
    assert (config.linear_num_key_heads, config.linear_num_value_heads, config.linear_key_head_dim) == (16, 32, 128)
    assert (config.linear_value_head_dim, config.linear_conv_kernel_dim, config.chunk) == (128, 4, gated_delta.CHUNK) == (128, 4, 64)
    assert (config.n_routed_experts, config.held, config.num_experts_per_tok, config.n_shared_experts) == (512, (0, 256), 10, 1)
    assert (config.moe_intermediate_size, config.shared_intermediate_size, config.vocab_size) == (512, 512, 75968)
    assert config.scoring_func == "softmax" and config.shared_expert_combination_strategy == "sigmoid_gate"
    assert (config.rotary_pct, config.rope_theta, config.norm_eps, config.use_qk_norm) == (0.25, 10_000_000, 1e-6, True)
    shapes = _trunk.param_shapes(config)
    ffn, attn = shapes["layers"][0]["ffn"], shapes["layers"][0]["attn"]
    assert ffn["router"][0] == (2048, 512) and ffn["shared_gate"][0] == (2048, 1) and "bias" not in ffn
    assert attn["w_qkvz"][0] == (2048, 12288) and attn["w_ba"][0] == (2048, 64) and attn["conv"][0] == (4, 8192)
    assert shapes["layers"][3]["attn"]["wq"][0] == (2048, 16, 512) and shapes["final_norm"][1] == "offset_gain"
    template = jax.eval_shape(lambda: _trunk.init_params(config, 0, jnp.bfloat16))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(template) == 3_522_030_656
    assert [count(layer) for layer in template["layers"]] == [843_225_280] * 3 + [836_770_304]
    assert count(template["layers"][0]["attn"]) == 33_718_464 and count(template["layers"][3]["attn"]) == 27_263_488


@pytest.mark.parametrize(
    "key, value", [("use_sliding_window", True), ("mlp_only_layers", [1]), ("decoder_sparse_step", 2)]
)
def test_qwen3_next_keys_without_a_block_are_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        TrunkConfig.from_dict(gdn_dict(**{key: value}), name="toy-gdn")


@pytest.mark.parametrize("rows, seed", [(3, 0), (5, 1), (8, 2)])
def test_gdn_forward_float32_matches_the_recurrence_over_several_chunks(gdn, gdn_runtime, rows, seed):
    body, config = gdn
    ids, mask = batch(rows, 128, seed)  # row 0 fills 128 positions: 8 chunks of 16
    assert mask.sum(axis=1).max() == 8 * config.chunk
    got, info = gdn_runtime.forward(ids, mask)
    want = gdn_reference_rows(gdn_runtime.params, ids, mask, body)
    assert np.linalg.norm(got - want, axis=1).max() < F32_TOL
    assert info["gdn_chunks_useful"] == 3 * sum(-(-int(t) // 16) for t in mask.sum(axis=1))
    assert info["gdn_chunks_visited"] == 3 * info["batch_bucket"] * 8 and "ssm_chunks_useful" not in info
    assert info["attn_pairs_allowed"] == sum(block_attention.pairs_allowed(int(t), None) for t in mask.sum(axis=1))
    # a reference that loses the state between chunks is somebody else's vectors
    lost = gdn_reference_rows(gdn_runtime.params, ids[:2], mask[:2], body, mode="no_carry")
    assert np.linalg.norm(lost - want[:2], axis=1).min() > 50 * F32_TOL


def test_gdn_forward_bfloat16_follows_its_own_experts(gdn):
    body, config = gdn
    runtime = TrunkRuntime(config, max_len=128)
    runtime.params = gdn_params(config, 8, jnp.bfloat16)
    ids, mask = batch(4, 64, 3)
    got, info = runtime.forward(ids, mask, routing=True)
    choice = info["expert_choice"]
    assert choice.shape == (4, 4, 64, 3) and ((choice >= 0).all(axis=-1) == (mask > 0)[None]).all()
    want = np.stack(
        [
            np.asarray(gdn_ref.encode(runtime.params, ids[i], int(mask[i].sum()), body, forced=choice[:, i])[0])
            for i in range(4)
        ]
    )
    assert np.linalg.norm(got - want, axis=1).max() < BF16_TOL


@pytest.fixture(scope="module")
def gdn_blocks(gdn):
    body, config = gdn
    params = gdn_params(config, 13)
    h = jax.random.normal(jax.random.PRNGKey(9), (2, 72, config.hidden_size), jnp.float32)
    return body, config, params, h


def test_gated_deltanet_block(gdn_blocks):
    body, config, params, h = gdn_blocks
    p = params["layers"][0]["attn"]
    got = _trunk.ATTENTION["gated_deltanet"].apply(p, h, config, {})
    for i in range(2):  # 72 positions: four whole chunks of 16 and a part of one
        with jax.default_matmul_precision("highest"):
            want = gdn_ref.gated_deltanet(p, h[i], body)
        assert np.abs(np.asarray(got[i]) - np.asarray(want)).max() < 2e-4
    # the convolution without a bias is the convolution with a zero bias
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 3))
    taps = jax.random.normal(jax.random.PRNGKey(2), (4, 3))
    assert np.allclose(_trunk.causal_conv(x, taps), _trunk.causal_conv(x, taps, jnp.zeros(3)))


def test_gated_attention_block(gdn_blocks):
    body, config, params, h = gdn_blocks
    p = params["layers"][3]["attn"]
    ctx = {"rope_half": _trunk.interleaved_rope_tables(config, 72, 8)}
    got = _trunk.ATTENTION["gqa_gated"].apply(p, h, config, ctx)
    for i in range(2):
        with jax.default_matmul_precision("highest"):
            want = gdn_ref.attention(p, h[i], body)
        assert np.abs(np.asarray(got[i]) - np.asarray(want)).max() < 2e-4


def _unrotated(body):
    return dict(body, partial_rotary_factor=0.5)


def _ungated_attention(params):
    layers = list(params["layers"])
    wq = layers[3]["attn"]["wq"]
    layers[3] = dict(layers[3], attn=dict(layers[3]["attn"], wq=wq.at[..., wq.shape[-1] // 2 :].set(0.0)))
    return dict(params, layers=layers)


def _ungated_shared(params):
    return dict(
        params,
        layers=[dict(layer, ffn=dict(layer["ffn"], shared_gate=layer["ffn"]["shared_gate"] * 0.0)) for layer in params["layers"]],
    )


@pytest.mark.parametrize("change", ["partial_rotary", "output_gate", "offset_norm", "shared_gate"])
def test_each_new_piece_changes_the_result(gdn, gdn_runtime, change, monkeypatch):
    """The rotary's share of the dims, the output gate, the 1 of ``1 + w`` and
    the shared expert's gate each move the vectors when changed, and (where
    the change is one of the model's) the reference moves with them."""
    body, config = gdn
    ids, mask = batch(2, 64, 4)
    before = gdn_runtime.forward_ids(ids, mask)
    params, changed_body = gdn_runtime.params, body
    if change == "partial_rotary":
        changed_body = _unrotated(body)
    elif change == "output_gate":
        params = _ungated_attention(params)  # sigmoid(0): every gate at one half
    elif change == "shared_gate":
        params = _ungated_shared(params)
    runtime = TrunkRuntime(TrunkConfig.from_dict(changed_body, name="toy-gdn"), max_len=128, dtype=jnp.float32)
    runtime.params = params
    if change == "offset_norm":  # a zero-centred norm that forgets its 1: the gain is w alone
        monkeypatch.setitem(_trunk.NORM, "rms_offset", _trunk.rms_norm)
    after = runtime.forward_ids(ids, mask)
    assert np.linalg.norm(after - before, axis=1).min() > 1e-3
    if change != "offset_norm":
        want = gdn_reference_rows(params, ids, mask, changed_body)
        assert np.linalg.norm(after - want, axis=1).max() < F32_TOL


def test_gdn_padding_and_companions_change_no_vector(gdn_runtime):
    ids, mask = batch(rows=3, width=64, seed=5)
    together = gdn_runtime.forward_ids(ids, mask)
    for i in range(3):
        alone = gdn_runtime.forward_ids(ids[i : i + 1], mask[i : i + 1])
        assert np.abs(alone[0] - together[i]).max() < 1e-5
    wide, info = gdn_runtime.forward(np.pad(ids, ((0, 0), (0, 64))), np.pad(mask, ((0, 0), (0, 64))))
    assert info["len_bucket"] == 128 and np.abs(wide - together).max() < 1e-5


@pytest.mark.parametrize("layer, kind", [(0, "linear"), (3, "full")])
def test_the_two_shares_of_a_qwen3_next_layer_add_up_to_the_uncut_layer(layer, kind):
    """Sixteen experts over two shares of 8 (the deployment's: experts 0-255
    and 256-511 of 512): what the shares' routed parts give, with the mixer,
    the router, the shared expert, its gate and x counted once, is the uncut
    layer as the reference computes it."""
    whole_body = gdn_dict(num_experts=16, experts_held=None, published={})
    whole = TrunkConfig.from_dict(whole_body, name="uncut")
    assert whole.held == (0, 16) and whole.n_routed_experts == 16
    params = gdn_params(whole, 17)
    p = params["layers"][layer]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, whole.hidden_size), jnp.float32)
    block = _trunk.ATTENTION[whole.layer_table()[layer].attention]
    ctx = {"rope_half": _trunk.interleaved_rope_tables(whole, 40, 8)}
    mixed = np.asarray(block.apply(p["attn"], _trunk.norm(x, p["attn_norm"], whole), whole, ctx))[0]
    after = x + mixed[None]
    flat = _trunk.norm(after, p["ffn_norm"], whole).reshape(-1, whole.hidden_size)
    valid = jnp.ones(flat.shape[0], bool)
    parts = []
    for first in (0, 8):
        routed, _counts, _choice = moe.expert_layer(
            flat, valid, p["ffn"]["router"], None, p["ffn"]["w_gate"][first : first + 8],
            p["ffn"]["w_up"][first : first + 8], p["ffn"]["w_down"][first : first + 8],
            top_k=3, scale=1.0, experts_held=(first, 8), scoring="softmax",
        )
        parts.append(np.asarray(routed))
    assert all(np.abs(part).max() > 0 for part in parts)
    gate = jax.nn.sigmoid(flat @ p["ffn"]["shared_gate"])
    shared = np.asarray(gate * _trunk._gated_ffn(p["ffn"]["shared"], flat))
    with jax.default_matmul_precision("highest"):
        want, _logits = gdn_ref.layer(p, x[0], None, whole_body, kind)
    assert np.abs(np.asarray(after[0]) + shared + sum(parts) - np.asarray(want)).max() < 2e-4
    # and a cut layer is the program's own layer on its share
    cut = TrunkConfig.from_dict(gdn_dict(experts_held=[8, 8]), name="cut")
    held = dict(p, ffn={k: (v[8:16] if k in ("w_gate", "w_up", "w_down") else v) for k, v in p["ffn"].items()})
    ctx.update(valid=valid, expert_counts=[], expert_choice=[])

    def attend(p_attn, u):
        return block.apply(p_attn, u, cut, ctx)

    def feed(p_ffn, u):
        return _trunk.FFN["moe"].apply(p_ffn, u, cut, ctx)

    got = np.asarray(_trunk.RESIDUAL["add"].layer(held, x, attend, feed, cut))[0]
    assert np.abs(got - (np.asarray(after[0]) + shared + parts[1])).max() < 2e-4
