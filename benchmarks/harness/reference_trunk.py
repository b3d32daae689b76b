"""The plain reference of the trunk configurations: Xing4.0-29B-A4B as an embedder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no sort, no cache, no pad ladder, nothing imported from the program.
The parameters arrive as the program holds them (bfloat16); one layer's are
upcast at a time, inside that layer's function, and the texts go through in
blocks of ``rows``, so the whole model never stands in float32.
``mode="fp8"`` is the control (every matmul operand rounded to e4m3, as
``reference.py`` does it); it never decides ``correct``.

Sizes (published): d hidden, H heads, d_n / d_r / d_v head dims (no-rope,
rope, value), r_q / r_kv latent ranks, n = ``hc_mult`` streams, E experts,
k a token, f expert width, I dense width. ``RMS`` is RMS norm, eps
``rms_norm_eps``, with its own gain.

**Streams.** A token's embedding is copied into n streams, X_0 in R^{n x d}.
*assumed*: copy on entry, sum of the streams before the final norm on exit
(Hyper-Connections, arXiv:2409.19606).

**Residual around every sub-layer F** (attention, then feed-forward;
arXiv:2512.24880). x = flatten(X) in R^{nd} (stream-major); x' = RMS(x);
H~_pre = a_pre (x' P_pre) + b_pre in R^n; H~_post = a_post (x' P_post) +
b_post in R^n; H~_res = a_res mat(x' P_res) + b_res in R^{n x n} (row-major);
H_pre = sigmoid(H~_pre); H_post = 2 sigmoid(H~_post); H_res =
Sinkhorn(exp(clip(H~_res, ``mhc_h_res_clamp_min``, ``_max``))),
``hc_sinkhorn_iters`` rounds, each a row normalisation and then a column
normalisation, denominators + ``hc_eps``. u = H_pre X in R^d;
X <- H_res X + H_post^T F(RMS(u)), the inner RMS being the sub-layer's own
input norm. *assumed*: row-then-column order; ``hc_eps`` in the
denominators; b_pre seeded at logit(1/n), b_post at 0, b_res at 2 I (H_res
starts near the identity, diagonal about 0.7), each with seeded noise; a_* at
0.5 (``weights_trunk.py`` says why not the 0.01 training starts from).

**MLA, expanded.** c_q = RMS(h W_qa); [q_n | q_r] = c_q W_qb per head;
[c_kv | k_r] = h W_kva; [k_n | v] = RMS(c_kv) W_kvb per head; rotary on q_r
and on the one k_r all heads share, YaRN as DeepSeek-V3's modelling code
applies it (pairs de-interleaved, then rotated by halves; frequencies blended
between ``beta_fast`` and ``beta_slow`` rotations over ``factor`` from
``original_max_position_embeddings``; cos and sin times mscale(factor,
``mscale``) / mscale(factor, ``mscale_all_dim``); softmax scale
(d_n + d_r)^-1/2 x m^2, m = 0.1 ``mscale_all_dim`` ln(factor) + 1); causal
softmax over [q_n | q_r] . [k_n | k_r]; out = concat_h(P v) W_o. No bias, no
cache.

**Dense feed-forward.** W_down(silu(W_gate h) * W_up h), width I.

**Expert feed-forward.** s = sigmoid(h W_r); choice = top-k of s + bias_e
(``noaux_tc``, one group); w = ``routed_scaling_factor`` x s[choice] /
sum s[choice]; y = sum_e w_e Expert_e(h) + Shared(h), each a gated silu FFN
of width f (the shared one f x ``n_shared_experts``). No token is dropped.
Here: a loop over the experts, each applied to every token and weighted by
the token's (mostly zero) weight for it. *assumed*: bias_e seeded gaussian,
sigma 0.01.

**A choice that is followed.** A sparse layer is not continuous in its input:
where a token's k-th and (k+1)-th corrected scores nearly tie, rounding
upstream decides which expert it goes to, and either is a sound answer, as
either of two tied rows is a sound top-k. So the reference can be told which
experts to follow (``forced``: the experts the timed path's router chose),
computes the weights of those experts from *its own* scores, and hands back
its corrected scores of every expert at every token, from which the caller
reads how far below the reference's own k-th best the followed experts lie.
The vectors then differ by arithmetic alone; the choice is judged apart.

**Pooling.** RMS of the summed streams (final gain), the vector at the last
real position, L2-normalised. *assumed*: the published model is a generator;
this is how decoder-derived embedders pool. **Left out**, as in the program:
the multi-token-prediction layer and the output head.

The layer pattern: layers below ``first_k_dense_replace`` (and those off
``moe_layer_freq``) are dense, the others sparse. Tokens: ``reference.py``'s
hashing tokenizer (CLS, then one hashed id a token).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference import PAD_ID, _einsum, tokenize

F32 = jnp.float32


def _up(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


# -- rotary -------------------------------------------------------------------


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotary_tables(config: dict, length: int):
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    inv_freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0
    yarn = config.get("rope_scaling")
    if yarn and yarn.get("type") == "yarn":
        factor, original = float(yarn["factor"]), float(yarn["original_max_position_embeddings"])

        def dim_of(rotations):
            return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
        high = min(math.ceil(dim_of(yarn["beta_slow"])), dim - 1)
        high = high + 0.001 if low == high else high
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        extrapolated, interpolated = inv_freq, inv_freq / factor
        inv_freq = interpolated * ramp + extrapolated * (1.0 - ramp)
        scale = _mscale(factor, yarn.get("mscale", 1)) / _mscale(factor, yarn.get("mscale_all_dim", 0))
    angles = np.outer(np.arange(length, dtype=np.float64), inv_freq)
    angles = np.concatenate([angles, angles], axis=1)
    return jnp.asarray(np.cos(angles) * scale, F32), jnp.asarray(np.sin(angles) * scale, F32)


def attention_scale(config: dict) -> float:
    scale = (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])) ** -0.5
    yarn = config.get("rope_scaling")
    if yarn and yarn.get("mscale_all_dim"):
        scale *= _mscale(float(yarn["factor"]), yarn["mscale_all_dim"]) ** 2
    return scale


def rotate(x, cos, sin):
    """x [B, T, (H,) d_r]; cos, sin [T, d_r]."""
    x = jnp.concatenate([x[..., ::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    return x * cos + turned * sin


# -- the blocks, each on float32 parameters --------------------------------------


def mla(p, h, config: dict, mode="f32"):
    """h [B, T, d] -> [B, T, d]."""
    eps = float(config["rms_norm_eps"])
    d_n, r_kv = int(config["qk_nope_head_dim"]), int(config["kv_lora_rank"])
    cos, sin = rotary_tables(config, h.shape[1])
    c_q = rms(_einsum("btd,dr->btr", h, p["wq_a"], mode), p["q_norm"], eps)
    q = _einsum("btr,rhe->bthe", c_q, p["wq_b"], mode)
    q = jnp.concatenate([q[..., :d_n], rotate(q[..., d_n:], cos, sin)], axis=-1)
    kv_a = _einsum("btd,de->bte", h, p["wkv_a"], mode)
    kv = _einsum("btr,rhe->bthe", rms(kv_a[..., :r_kv], p["kv_norm"], eps), p["wkv_b"], mode)
    k_r = rotate(kv_a[..., r_kv:], cos, sin)
    heads = kv.shape[2]
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_r[:, :, None, :], k_r.shape[:2] + (heads, k_r.shape[-1]))],
        axis=-1,
    )
    v = kv[..., d_n:]
    logits = _einsum("bqhe,bkhe->bhqk", q, k, mode) * attention_scale(config)
    length = h.shape[1]
    seen = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]
    logits = jnp.where(seen[None, None], logits, -jnp.inf)
    mixed = _einsum("bhqk,bkhe->bqhe", jax.nn.softmax(logits, axis=-1), v, mode)
    return _einsum("bqhe,hed->bqd", mixed, p["wo"], mode)


def gated_ffn(p, h, mode="f32"):
    gate = _einsum("...d,df->...f", h, p["w_gate"], mode)
    up = _einsum("...d,df->...f", h, p["w_up"], mode)
    return _einsum("...f,fd->...d", jax.nn.silu(gate) * up, p["w_down"], mode)


def router_weights(p, h, config: dict, mode="f32", forced=None):
    """The dense [T, E] matrix of each token's weight for each expert (zero
    for the experts it was not sent to), and the corrected scores [T, E] the
    choice is made from. ``forced`` [T, k] names the experts to follow in
    place of the top-k (-1: none)."""
    k = int(config["num_experts_per_tok"])
    scores = jax.nn.sigmoid(_einsum("td,de->te", h, p["router"], mode))
    corrected = scores + p["bias"]
    if forced is None:
        chosen = corrected >= jnp.sort(corrected, axis=-1)[:, -k][:, None]
    else:
        chosen = (forced[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(axis=1)
    picked = jnp.where(chosen, scores, 0.0)
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return picked * float(config["routed_scaling_factor"]), corrected


def expert_ffn(p, h, config: dict, mode="f32", experts_held=None, shared=True, forced=None):
    """h [T, d] -> ([T, d], corrected scores [T, E]). ``experts_held=(first,
    count)`` keeps the routed part of those experts only (``p``'s expert
    weights are theirs); ``shared=False`` leaves the shared expert out."""
    weights, corrected = router_weights(p, h, config, mode, forced)
    first, count = experts_held or (0, weights.shape[1])

    def one(total, expert):
        w_gate, w_up, w_down, column = expert  # one expert's weights, upcast here
        out = gated_ffn(_up({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}), h, mode)
        return total + column[:, None] * out, None

    columns = weights[:, first : first + count].T
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], columns)
    )
    if shared:
        total = total + gated_ffn(_up(p["shared"]), h, mode)
    return total, corrected


def sinkhorn(matrix, iters: int, eps: float):
    """matrix [..., n, n] positive; rows, then columns, ``iters`` times."""
    for _ in range(iters):
        matrix = matrix / (matrix.sum(axis=-1, keepdims=True) + eps)
        matrix = matrix / (matrix.sum(axis=-2, keepdims=True) + eps)
    return matrix


def residual_coefficients(p, streams, config: dict, mode="f32"):
    """streams [B, T, n, d] -> H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n]."""
    n = streams.shape[2]
    flat = streams.reshape(streams.shape[:2] + (-1,))
    normed = rms(flat, p["norm"].reshape(-1), float(config["rms_norm_eps"]))
    raw = _einsum("btx,xc->btc", normed, p["proj"].reshape(flat.shape[-1], -1), mode)
    a_pre, a_post, a_res = p["alpha"][0], p["alpha"][1], p["alpha"][2]
    bias = p["bias"]
    pre = a_pre * raw[..., :n] + bias[:n]
    post = a_post * raw[..., n : 2 * n] + bias[n : 2 * n]
    res = (a_res * raw[..., 2 * n :] + bias[2 * n :]).reshape(raw.shape[:2] + (n, n))
    res = jnp.clip(res, float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"]))
    h_res = sinkhorn(jnp.exp(res), int(config["hc_sinkhorn_iters"]), float(config["hc_eps"]))
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res


def residual(p, streams, sublayer, config: dict, mode="f32"):
    """X <- H_res X + H_post^T F(u), u = H_pre X; ``sublayer`` is F, its own
    input norm included."""
    h_pre, h_post, h_res = residual_coefficients(p, streams, config, mode)
    u = jnp.einsum("btn,btnd->btd", h_pre, streams, precision="highest")
    out = sublayer(u)
    kept = jnp.einsum("btij,btjd->btid", h_res, streams, precision="highest")
    return kept + h_post[..., None] * out[:, :, None, :]


# -- the forward, layer by layer ---------------------------------------------------


def layer_is_sparse(config: dict, i: int) -> bool:
    return (
        int(config.get("n_routed_experts", 0)) > 0
        and i >= int(config["first_k_dense_replace"])
        and i % int(config.get("moe_layer_freq", 1)) == 0
    )


def layer(p, streams, forced, config: dict, mode="f32"):
    """One layer on streams [B, T, n, d]; ``p`` as the program holds it.
    ``forced`` [B, T, k] or None: the experts a sparse layer follows. Returns
    the new streams and, for a sparse layer, the router's corrected scores
    [B, T, E] (None for a dense one)."""
    eps = float(config["rms_norm_eps"])
    small = _up({k: p[k] for k in ("attn_res", "attn_norm", "attn", "ffn_res", "ffn_norm")})
    scores = []

    def attend(u):
        return mla(small["attn"], rms(u, small["attn_norm"], eps), config, mode)

    def feed(u):
        h = rms(u, small["ffn_norm"], eps)
        if "router" not in p["ffn"]:
            return gated_ffn(_up(p["ffn"]), h, mode)
        ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(F32), bias=p["ffn"]["bias"].astype(F32))
        follow = None if forced is None else forced.reshape(-1, forced.shape[-1])
        out, corrected = expert_ffn(ffn, h.reshape(-1, h.shape[-1]), config, mode, forced=follow)
        scores.append(corrected.reshape(h.shape[:2] + corrected.shape[-1:]))
        return out.reshape(h.shape)

    streams = residual(small["attn_res"], streams, attend, config, mode)
    streams = residual(small["ffn_res"], streams, feed, config, mode)
    return streams, (scores[0] if scores else None)


def pool(streams, final_norm, last, eps):
    picked = jnp.take_along_axis(streams.sum(axis=2), last[:, None, None], axis=1)[:, 0]
    pooled = rms(picked, final_norm.astype(F32), eps)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-12)


@functools.lru_cache(maxsize=8)
def _programs(config_json: str, mode: str):
    config = json.loads(config_json)
    return (
        jax.jit(functools.partial(layer, config=config, mode=mode)),
        jax.jit(functools.partial(pool, eps=float(config["rms_norm_eps"]))),
    )


def encode(params, ids, mask, config: dict, mode="f32", forced=None):
    """ids, mask [B, T] right-padded -> (unit vectors [B, d], the sparse
    layers' corrected router scores [sparse layers, B, T, E]). ``forced``
    [sparse layers, B, T, k]: the experts each token follows (-1: none) in
    place of the reference's own top-k."""
    layer_fn, pool_fn = _programs(json.dumps(config, sort_keys=True), mode)
    n = int(config["hc_mult"])
    last = jnp.maximum(jnp.asarray(mask).sum(axis=1).astype(jnp.int32) - 1, 0)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)].astype(F32)
        streams = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n, x.shape[-1]))
        scores = []
        for i, p in enumerate(params["layers"]):
            sparse = layer_is_sparse(config, i)
            if ("router" in p["ffn"]) != sparse:
                raise ValueError(f"layer {i}: the parameters and the configuration's layer pattern disagree")
            follow = jnp.asarray(forced[len(scores)], jnp.int32) if sparse and forced is not None else None
            streams, corrected = layer_fn(p, streams, follow)
            if sparse:
                scores.append(corrected)
        experts = int(config.get("n_routed_experts", 0))
        stacked = jnp.stack(scores) if scores else jnp.zeros((0,) + tuple(ids.shape) + (experts,), F32)
        return pool_fn(streams, params["final_norm"], last), stacked


def embed(params, texts, config: dict, *, max_len: int, mode="f32", forced=None, rows=8, least=64):
    """Unit vectors [len(texts), d] of the texts and the corrected router
    scores [sparse layers, len(texts), max_len, E] (NaN where a text has no
    token), in blocks of ``rows`` texts padded to the power of two that holds
    the block's longest, ``least`` or more. ``forced`` [sparse layers,
    len(texts), positions, k]: the experts to follow, as ``encode`` takes them."""
    vectors, scores = [], []
    for start in range(0, len(texts), rows):
        encoded = [tokenize(t, int(config["vocab_size"]), max_len) for t in texts[start : start + rows]]
        width = max(least, 1 << (max(len(e) for e in encoded) - 1).bit_length())
        ids = np.full((rows, width), PAD_ID, dtype=np.int32)
        mask = np.zeros((rows, width), dtype=np.float32)
        for i, e in enumerate(encoded):
            ids[i, : len(e)] = e
            mask[i, : len(e)] = 1.0
        follow = None
        if forced is not None:
            part = np.asarray(forced)[:, start : start + rows, :width]
            follow = np.full(part.shape[:1] + (rows, width) + part.shape[3:], -1, np.int32)
            follow[:, : part.shape[1], : part.shape[2]] = part
        vecs, corrected = encode(params, ids, mask, config, mode, follow)
        vectors.append(np.asarray(vecs)[: len(encoded)])
        real = mask[None, : len(encoded), :max_len, None] > 0
        block = np.full(corrected.shape[:1] + (len(encoded), max_len) + corrected.shape[3:], np.nan, np.float32)
        block[:, :, :width] = np.where(real, np.asarray(corrected)[:, : len(encoded), :max_len], np.nan)
        scores.append(block)
    if not vectors:
        return np.zeros((0, int(config["hidden_size"])), np.float32), np.zeros((0, 0, max_len, 0), np.float32)
    return np.concatenate(vectors), np.concatenate(scores, axis=1)
