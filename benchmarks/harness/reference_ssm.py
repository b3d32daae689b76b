"""The plain reference of the hybrid state-space configurations:
granite-4.0-h-small (``model_type`` ``granitemoehybrid``) as an embedder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no chunks, no sort, no cache, no pad ladder, nothing imported from the
program. The parameters arrive as the program holds them (bfloat16); a
layer's are upcast inside that layer's function, one routed expert at a
time, and the texts go through one at a time. The Mamba-2 layer is the
**recurrence, position by position** (``lax.scan`` over the row, a state
[128, 64, 128] a step): nothing of the program's chunked algebra is in it.
``mode="fp8"`` is the control (every matmul operand, and the scan's ``x``,
``B`` and ``C``, rounded to e4m3, as ``reference.py`` does it);
``mode="no_carry"`` is the state's control (float32, the state set to zero at
every ``mamba_chunk_size``-th position: what a scan that loses its carry
between chunks computes); neither decides ``correct``.

Every size as published: d 4096; Mamba-2 with 128 heads of 64, state 128, one
group, a convolution over 4 positions; 32 query and 8 key-value heads of
128; 72 experts of width 768, 10 a token, one shared expert of width 1536.
With m = ``residual_multiplier`` 0.22, ``rms`` with eps 1e-5 and a gain:

    x_0 = embedding_multiplier * E[ids]                                   12 * E
    x <- x + m * mix_i(rms(x));   x <- x + m * (moe(h) + shared(h)),  h = rms(x)

    layer_types[i] == "mamba":
        [z | xBC | dt] = h W_in        4096 -> 8192 + (8192 + 128 + 128) + 128, no bias
        xBC <- silu(conv(xBC))         depthwise, causal, the last 4 positions (the token's own included), a bias
        x [T, 128, 64], B [T, 128], C [T, 128] = split(xBC)
        dt_t = softplus(dt_t + dt_bias) [128];   A = -exp(A_log) [128]
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t  (each head; S in R^{64 x 128}, S_{-1} = 0)
        y_t = S_t C_t + D x_t
        mix = (rms_8192(y * silu(z)) * g) W_out          one group: the mean of squares over all 8192
    layer_types[i] == "attention":
        q, k, v = h Wq [T,32,128], h Wk [T,8,128], h Wv [T,8,128]        no bias, no rotary ("nope")
        query head j reads key-value head j // 4;  allowed(t, s): s <= t
        mix = concat_heads(softmax(attention_multiplier * q k^T | allowed) v) Wo     1/128, not 1/sqrt(128)
    l   = h Wr in R^72;  C = the 10 largest;  w = softmax(l_C)
    moe = sum_{e in C, e held here} w_e E_e(h);   E(h) = (silu(h Wg) * (h Wu)) Wd, 4096 -> 768 -> 4096
    shared: the same form, 4096 -> 1536 -> 4096, added unweighted

After the last layer: rms (final gain) at the last real token, L2-normalised.

*Assumed* (the configuration file lists each): ``head_dim`` = 4096 / 32;
``intermediate_size`` is one routed expert's width; the router as above
(plain top-k of the logits, softmax over the chosen, no bias, no scaling);
one group in the gated norm; the pooling (the published model is a
generator). *The share*: this chip holds ``experts_held`` = (first, count) of
the ``published.num_local_experts`` the router scores; what the other chip's
experts would add is left out, here as in the program, and that partial
result goes on to the next layer. *Left out*: the output head and
``logits_scaling``, decoding, every cache and kept state.

**A choice that is followed** (``reference_trunk.py`` says why): told which
experts the timed path's router chose (``forced``), the reference weighs
those by its own logits and hands back its logits of every expert, so the
vectors differ by arithmetic alone and the choice is judged apart. With a
choice to follow each expert is applied to the tokens sent to it
(``nonzero``, padded to the busiest expert's count); without one, to every
token.

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

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128


def _arithmetic(mode: str) -> str:
    """The matmuls' mode: the state's control is float32 arithmetic."""
    return "f32" if mode == "no_carry" else mode


def head_dim(config: dict) -> int:
    return int(config.get("head_dim") or int(config["hidden_size"]) // int(config["num_attention_heads"]))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + eps) * gain


def mamba(p, h, config: dict, mode="f32"):
    """h [T, d] -> [T, d]: the recurrence, one position a step."""
    heads, width, states = (int(config[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state"))
    inner, length, einsum_mode = heads * width, h.shape[0], _arithmetic(mode)
    projected = _einsum("td,de->te", h, p["w_in"], einsum_mode)
    z, xbc, dt = projected[:, :inner], projected[:, inner : 2 * inner + 2 * states], projected[:, -heads:]
    taps = p["conv"]  # [4, channels]: taps[-1] is the token's own
    before = jnp.pad(xbc, ((taps.shape[0] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_bias"] + sum(before[j : j + length] * taps[j] for j in range(taps.shape[0])))
    x, b, c = xbc[:, :inner].reshape(length, heads, width), xbc[:, inner : inner + states], xbc[:, inner + states :]
    if mode == "fp8":
        x, b, c = _fp8(x), _fp8(b), _fp8(c)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a, skip = -jnp.exp(p["A_log"]), p["D"]
    forget_every = int(config["mamba_chunk_size"]) if mode == "no_carry" else 0

    def step(state, at):  # state [heads, width, states]
        t, x_t, dt_t, b_t, c_t = at
        if forget_every:
            state = jnp.where(t % forget_every == 0, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t, precision=HIGHEST) + skip[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, width, states), F32), (jnp.arange(length), x, dt, b, c))
    gated = y.reshape(length, inner) * jax.nn.silu(z)
    return _einsum("te,ed->td", rms_norm(gated, p["norm"], float(config["rms_norm_eps"])), p["w_out"], einsum_mode)


def attention(p, h, config: dict, mode="f32"):
    """h [T, d] -> [T, d]: grouped-query, unrotated, causal over the whole row."""
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    width, length, mode = head_dim(config), h.shape[0], _arithmetic(mode)
    scale = float(config["attention_multiplier"])
    q = _einsum("td,dhe->the", h, p["wq"], mode)
    k = jnp.repeat(_einsum("td,dhe->the", h, p["wk"], mode), heads // kv_heads, axis=1)
    v = jnp.repeat(_einsum("td,dhe->the", h, p["wv"], mode), heads // kv_heads, axis=1)
    block = min(QUERY_BLOCK, length)
    position = jnp.arange(length)

    def one_block(start):
        queries = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        allowed = position[None, :] <= (start + jnp.arange(block))[:, None]
        logits = _einsum("qhe,khe->hqk", queries, k, mode) * scale
        probs = jax.nn.softmax(jnp.where(allowed[None], logits, -jnp.inf), axis=-1)
        return _einsum("hqk,khe->qhe", probs, v, mode)

    mixed = jax.lax.map(one_block, jnp.arange(0, length, block)).reshape(length, heads, width)
    return _einsum("the,hed->td", mixed, p["wo"], mode)


def router_weights(p, h, config: dict, mode="f32", forced=None):
    """Each token's weight for each expert [T, E] (zero where it was not sent
    there), which experts it was sent to [T, E], and the logits [T, E] the
    choice is made from. ``forced`` [T, k] names the experts to follow in
    place of the k largest (-1: none)."""
    k = int(config["num_experts_per_tok"])
    logits = _einsum("td,de->te", h, p["router"], mode)
    if forced is None:
        chosen = logits >= jnp.sort(logits, axis=-1)[:, -k][:, None]
    else:
        chosen = (forced[:, :, None] == jnp.arange(logits.shape[1])[None, None, :]).any(axis=1)
    weights = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    return jnp.where(chosen.any(axis=-1, keepdims=True), weights, 0.0), chosen, logits


def expert_ffn(p, h, config: dict, mode="f32", experts_held=None, shared=True, forced=None, busiest=None):
    """h [T, d] -> ([T, d], logits [T, E]). ``experts_held=(first, count)``
    keeps the routed part of those experts only (``p``'s expert weights are
    theirs); ``shared=False`` leaves the shared expert out. ``busiest``: no
    held expert takes more tokens than that (each is then applied to its own
    tokens only); None applies each to every token."""
    mode = _arithmetic(mode)
    weights, chosen, logits = router_weights(p, h, config, mode, forced)
    first, count = experts_held or (0, weights.shape[1])

    def one(total, expert):
        w_gate, w_up, w_down, column, sent = expert  # one expert's weights, upcast here
        own = _up({"w_gate": w_gate, "w_up": w_up, "w_down": w_down})
        if busiest is None:
            return total + column[:, None] * gated_ffn(own, h, mode), None
        at = jnp.nonzero(sent, size=busiest, fill_value=0)[0]
        weight = jnp.where(jnp.arange(busiest) < sent.sum(), column[at], 0.0)
        return total.at[at].add(weight[:, None] * gated_ffn(own, h[at], mode)), None

    held = slice(first, first + count)
    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], weights[:, held].T, chosen[:, held].T)
    )
    if shared:
        total = total + gated_ffn(_up(p["shared"]), h, mode)
    return total, logits


def layer(p, x, forced, config: dict, kind: str, mode="f32", busiest=None):
    """One layer on x [T, d]; ``p`` as the program holds it; ``kind`` is the
    layer's entry of ``layer_types``; ``forced`` [T, k] or None. Returns the
    new x and the router's logits [T, E]."""
    eps, m = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    h = rms_norm(x, p["attn_norm"].astype(F32), eps)
    mix = {"mamba": mamba, "attention": attention}[kind](_up(p["attn"]), h, config, mode)
    x = x + m * mix
    h = rms_norm(x, p["ffn_norm"].astype(F32), eps)
    ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(F32))
    held = config.get("experts_held")
    out, logits = expert_ffn(
        ffn, h, config, mode, experts_held=tuple(held) if held else None, forced=forced, busiest=busiest
    )
    return x + m * out, logits


def pool(x, final_norm, last, eps):
    pooled = rms_norm(x[last], final_norm.astype(F32), eps)
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
    return int(config.get("published", {}).get("num_local_experts", config["num_local_experts"]))


def encode(params, ids, length: int, config: dict, mode="f32", forced=None):
    """ids [T] of one text, right-padded, ``length`` of them real -> (unit
    vector [d], the layers' router logits [layers, T, E]). ``forced``
    [layers, T, k]: the experts each token follows (-1: none)."""
    kinds = list(config["layer_types"])[: int(config["num_hidden_layers"])]
    busiest = None
    if forced is not None:
        first, count = config.get("experts_held") or (0, routed_experts(config))
        sent = np.asarray(forced) - first
        most = max(int(np.bincount(layer[(layer >= 0) & (layer < count)], minlength=1).max()) for layer in sent)
        busiest = min(len(ids), 1 << max(most - 1, 0).bit_length())  # few distinct programs
    frozen = json.dumps(config, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        x = float(config["embedding_multiplier"]) * params["embed"][jnp.asarray(ids)].astype(F32)
        logits = []
        for i, (kind, p) in enumerate(zip(kinds, params["layers"])):
            layer_fn, pool_fn = _programs(frozen, kind, mode, busiest)
            follow = None if forced is None else jnp.asarray(forced[i], jnp.int32)
            x, layer_logits = layer_fn(p, x, follow)
            logits.append(layer_logits)
        return pool_fn(x, params["final_norm"], max(length - 1, 0)), jnp.stack(logits)


def embed(params, texts, config: dict, *, max_len: int, mode="f32", forced=None, least=64):
    """Unit vectors [len(texts), d] of the texts and the router logits
    [layers, len(texts), max_len, E] (NaN where a text has no token), one
    text at a time, padded to the power of two that holds it, ``least`` or
    more. ``forced`` [layers, len(texts), positions, k]: the experts to
    follow, as ``encode`` takes them."""
    layers, experts = int(config["num_hidden_layers"]), routed_experts(config)
    vectors = np.zeros((len(texts), int(config["hidden_size"])), np.float32)
    logits = np.full((layers, len(texts), max_len, experts), np.nan, np.float32)
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
