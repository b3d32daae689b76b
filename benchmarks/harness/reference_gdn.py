"""The plain reference of the gated-delta-rule configurations:
Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``) as an embedder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunks, no sort, no cache, no pad ladder, nothing imported from the
program. The parameters arrive as the program holds them (bfloat16); a
layer's are upcast inside that layer's function, one routed expert at a
time, and the texts go through one at a time. The Gated DeltaNet layer is the
**recurrence, position by position** (``lax.scan`` over the row, a state
[32, 128, 128] a step): nothing of the program's chunked WY algebra (the
triangular inverse, ``W``, ``U``) is in it. ``mode="fp8"`` is the control
(every matmul operand, and the recurrence's ``q``, ``k`` and ``v``, rounded
to e4m3, as ``reference.py`` does it); ``mode="no_carry"`` is the state's
control (float32, the state set to zero at every ``NO_CARRY_EVERY``-th
position, or the file's ``linear_chunk_size``: what a scan that loses its
carry between chunks computes); neither decides ``correct``.

Every size as published: d 2048; Gated DeltaNet with 16 key heads and 32
value heads of 128, a convolution over 4 positions; 16 query and 2 key-value
heads of 256; 512 experts of width 512, 10 a token, one shared expert of
width 512 behind a sigmoid gate. With ``norm(x) = x / rms(x) * (1 + w)``
(eps 1e-6, zero-centred):

    x <- x + mix_i(norm(x));   x <- x + moe(h) + sigmoid(h w_sg) shared(h),  h = norm(x)

    layer i linear ((i + 1) % 4 != 0):
        [q | k | v | z] = h W_qkvz     2048 -> 2048 + 2048 + 4096 + 4096, no bias;  [b | a] = h W_ba  2048 -> 32 + 32
        q k v <- silu(conv(q k v))     depthwise, causal, the last 4 positions (the token's own included), no bias
        q, k <- q / |q|, k / |k| a head (eps 1e-6);  q <- q / sqrt(128)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias);  value head j reads key head j // 2
        S_t = e^g_t S_{t-1} + beta_t k_t (v_t - e^g_t S_{t-1}^T k_t)^T    (S in R^{128 x 128}, S_{-1} = 0)
        o_t = S_t^T q_t
        mix = (rms_128(o) * w * silu(z)) W_out        a head; a plain gain
    layer i full:
        [q | gate] = h W_q [T, 16, 256 + 256];  k, v = h W_k, h W_v [T, 2, 256]
        q, k <- norm_256(q), norm_256(k) a head;  rotate-half on dims 0..63 (i with i + 32), theta 1e7
        query head j reads key-value head j // 8;  allowed(t, s): s <= t
        mix = concat_heads(softmax(q k^T / 16 | allowed) v * sigmoid(gate)) W_o
    l   = h Wr in R^512;  C = the 10 largest;  w = softmax(l_C)
    moe = sum_{e in C, e held here} w_e E_e(h);   E(h) = (silu(h Wg) * (h Wu)) Wd, 2048 -> 512 -> 2048

After the last layer: ``norm`` (final gain) at the last real token, L2-normalised.

*Assumed* (the configuration file lists each): the layouts ``[q | k | v |
z]`` and ``[b | a]``; the rotate-half pairing; the zero-centred gains and the
gated norm's plain gain; the pooling (the published model is a generator).
*The share*: this chip holds ``experts_held`` = (first, count) of the
``published.num_experts`` the router scores; what the other chip's experts
would add is left out, here as in the program, and that partial result goes
on to the next layer. *Left out*: the output head, multi-token prediction,
decoding, every cache and kept state.

**A choice that is followed** (``reference_trunk.py`` says why, and
``reference_ssm.expert_ffn`` does it): told which experts the timed path's
router chose, the reference weighs those by its own logits and hands back its
logits of every expert.

Tokens: ``reference.py``'s hashing tokenizer (CLS, then one hashed id a token).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference import PAD_ID, _einsum, _fp8, tokenize
from benchmarks.harness.reference_gqa import _up, gated_ffn
from benchmarks.harness.reference_ssm import QUERY_BLOCK, _arithmetic, expert_ffn

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NO_CARRY_EVERY = 64  # the family's chunk


def norm(x, w, eps):
    """Zero-centred RMS norm: x / rms(x) * (1 + w)."""
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) * (1.0 + w)


def l2(x, eps=1e-6):
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + eps)


def layer_kinds(config: dict) -> list[str]:
    interval = int(config["full_attention_interval"])
    return ["linear" if (i + 1) % interval else "full" for i in range(int(config["num_hidden_layers"]))]


def gated_deltanet(p, h, config: dict, mode="f32"):
    """h [T, d] -> [T, d]: the recurrence, one position a step."""
    key_heads, heads = int(config["linear_num_key_heads"]), int(config["linear_num_value_heads"])
    dk, dv = int(config["linear_key_head_dim"]), int(config["linear_value_head_dim"])
    key, value, length, einsum_mode = key_heads * dk, heads * dv, h.shape[0], _arithmetic(mode)
    projected = _einsum("td,de->te", h, p["w_qkvz"], einsum_mode)
    qkv, z = projected[:, : 2 * key + value], projected[:, 2 * key + value :].reshape(length, heads, dv)
    ba = _einsum("td,de->te", h, p["w_ba"], einsum_mode)
    taps = p["conv"]  # [4, channels]: taps[-1] is the token's own
    before = jnp.pad(qkv, ((taps.shape[0] - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(before[j : j + length] * taps[j] for j in range(taps.shape[0])))
    q = l2(qkv[:, :key].reshape(length, key_heads, dk)) * dk**-0.5
    k = l2(qkv[:, key : 2 * key].reshape(length, key_heads, dk))
    v = qkv[:, 2 * key :].reshape(length, heads, dv)
    if mode == "fp8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    q, k = (jnp.repeat(a, heads // key_heads, axis=1) for a in (q, k))  # value head j reads key head j // 2
    beta = jax.nn.sigmoid(ba[:, :heads])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, heads:] + p["dt_bias"])
    forget_every = int(config.get("linear_chunk_size") or NO_CARRY_EVERY) if mode == "no_carry" else 0

    def step(state, at):  # state [heads, dk, dv]
        t, q_t, k_t, v_t, g_t, b_t = at
        if forget_every:
            state = jnp.where(t % forget_every == 0, 0.0, state)
        state = jnp.exp(g_t)[:, None, None] * state
        predicted = jnp.einsum("hkv,hk->hv", state, k_t, precision=HIGHEST)
        state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - predicted)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), F32), (jnp.arange(length), q, k, v, g, beta))
    eps = float(config["rms_norm_eps"])
    gated = o * jax.lax.rsqrt((o * o).mean(axis=-1, keepdims=True) + eps) * p["norm"] * jax.nn.silu(z)
    return _einsum("te,ed->td", gated.reshape(length, value), p["w_out"], einsum_mode)


def rotate_half(x, theta: float, dims: int):
    """x [T, H, e]: dims i and i + dims/2 of the first ``dims`` turned by t * theta^(-2i/dims)."""
    length, half = x.shape[0], dims // 2
    # the angles in float64 (the length is known when traced): at 16,384 positions float32 is 1e-3 off
    angles = np.arange(length, dtype=np.float64)[:, None] * theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
    cos, sin = (jnp.asarray(f(angles), F32)[:, None, :] for f in (np.cos, np.sin))
    a, b = x[..., :half], x[..., half:dims]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., dims:]], axis=-1)


def attention(p, h, config: dict, mode="f32"):
    """h [T, d] -> [T, d]: gated grouped-query attention, causal over the whole row."""
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    width, length, mode = int(config["head_dim"]), h.shape[0], _arithmetic(mode)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    dims = int(width * float(config["partial_rotary_factor"]))
    projected = _einsum("td,dhe->the", h, p["wq"], mode)
    q, gate = projected[..., :width], projected[..., width:]
    q = rotate_half(norm(q, p["q_norm"], eps), theta, dims)
    k = rotate_half(norm(_einsum("td,dhe->the", h, p["wk"], mode), p["k_norm"], eps), theta, dims)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(_einsum("td,dhe->the", h, p["wv"], mode), heads // kv_heads, axis=1)
    block = min(QUERY_BLOCK, length)
    position = jnp.arange(length)

    def one_block(start):
        queries = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        allowed = position[None, :] <= (start + jnp.arange(block))[:, None]
        logits = _einsum("qhe,khe->hqk", queries, k, mode) / np.sqrt(width)
        probs = jax.nn.softmax(jnp.where(allowed[None], logits, -jnp.inf), axis=-1)
        return _einsum("hqk,khe->qhe", probs, v, mode)

    mixed = jax.lax.map(one_block, jnp.arange(0, length, block)).reshape(length, heads, width)
    return _einsum("the,hed->td", mixed * jax.nn.sigmoid(gate), p["wo"], mode)


def layer(p, x, forced, config: dict, kind: str, mode="f32", busiest=None):
    """One layer on x [T, d]; ``p`` as the program holds it; ``kind`` is
    ``"linear"`` or ``"full"``; ``forced`` [T, k] or None. Returns the new x
    and the router's logits [T, E]."""
    eps = float(config["rms_norm_eps"])
    h = norm(x, p["attn_norm"].astype(F32), eps)
    mix = {"linear": gated_deltanet, "full": attention}[kind](_up(p["attn"]), h, config, mode)
    x = x + mix
    h = norm(x, p["ffn_norm"].astype(F32), eps)
    ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(F32))
    held = config.get("experts_held")
    routed, logits = expert_ffn(
        ffn, h, config, mode, experts_held=tuple(held) if held else None, shared=False, forced=forced,
        busiest=busiest,
    )
    arithmetic = _arithmetic(mode)
    gate = jax.nn.sigmoid(_einsum("td,de->te", h, ffn["shared_gate"].astype(F32), arithmetic))
    return x + routed + gate * gated_ffn(_up(ffn["shared"]), h, arithmetic), logits


def pool(x, final_norm, last, eps):
    pooled = norm(x[last], final_norm.astype(F32), eps)
    return pooled / (jnp.linalg.norm(pooled) + 1e-12)


@functools.lru_cache(maxsize=32)
def _programs(config_json: str, kind: str, mode: str, busiest):
    config = json.loads(config_json)
    return (
        jax.jit(functools.partial(layer, config=config, kind=kind, mode=mode, busiest=busiest)),
        jax.jit(functools.partial(pool, eps=float(config["rms_norm_eps"]))),
    )


def routed_experts(config: dict) -> int:
    """The router's width: the published count where the file is a share's."""
    return int(config.get("published", {}).get("num_experts", config["num_experts"]))


def encode(params, ids, length: int, config: dict, mode="f32", forced=None):
    """ids [T] of one text, right-padded, ``length`` of them real -> (unit
    vector [d], the layers' router logits [layers, T, E]). ``forced``
    [layers, T, k]: the experts each token follows (-1: none)."""
    busiest = None
    if forced is not None:
        first, count = config.get("experts_held") or (0, routed_experts(config))
        sent = np.asarray(forced) - first
        most = max(int(np.bincount(layer[(layer >= 0) & (layer < count)], minlength=1).max()) for layer in sent)
        busiest = min(len(ids), 1 << max(most - 1, 0).bit_length())  # few distinct programs
    frozen = json.dumps(config, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)].astype(F32)
        logits = []
        for i, (kind, p) in enumerate(zip(layer_kinds(config), params["layers"])):
            layer_fn, pool_fn = _programs(frozen, kind, mode, busiest)
            follow = None if forced is None else jnp.asarray(forced[i], jnp.int32)
            x, layer_logits = layer_fn(p, x, follow)
            logits.append(layer_logits)
        return pool_fn(x, params["final_norm"], max(length - 1, 0)), jnp.stack(logits)


def embed(params, texts, config: dict, *, max_len: int, mode="f32", forced=None, least=64):
    """Unit vectors [len(texts), d] of the texts and the router logits
    [layers, len(texts), max_len, E] float16 (NaN where a text has no token), one
    text at a time, padded to the power of two that holds it, ``least`` or
    more. ``forced`` [layers, len(texts), positions, k]: the experts to
    follow, as ``encode`` takes them."""
    layers, experts = int(config["num_hidden_layers"]), routed_experts(config)
    vectors = np.zeros((len(texts), int(config["hidden_size"])), np.float32)
    # float16 on the host: 512 logits a position and layer over a hundred texts of 16,384 positions
    logits = np.full((layers, len(texts), max_len, experts), np.nan, np.float16)
    for n, text in enumerate(texts):
        encoded = tokenize(text, int(config["vocab_size"]), max_len)
        width = max(least, 1 << (len(encoded) - 1).bit_length())
        ids = np.full(width, PAD_ID, dtype=np.int32)
        ids[: len(encoded)] = encoded
        follow = None
        if forced is not None:
            part = np.asarray(forced)[:, n, :width]
            follow = np.full((layers, width) + part.shape[2:], -1, np.int32)
            follow[:, : part.shape[1]] = part
            follow[:, len(encoded) :] = -1
        vector, text_logits = encode(params, ids, len(encoded), config, mode, follow)
        vectors[n] = np.asarray(vector)
        real = min(len(encoded), max_len)
        logits[:, n, :real] = np.asarray(text_logits)[:, :real]
    return vectors, logits
