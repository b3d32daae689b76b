"""The plain reference of the grouped-query trunk configurations:
command-a-plus-05-2026 (``model_type`` ``cohere2_moe``) as an embedder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no sort, no cache, no pad ladder, nothing imported from the program.
The parameters arrive as the program holds them (bfloat16); a layer's are
upcast inside that layer's function, one routed expert at a time, and the
texts go through one at a time, so neither the model nor a row's logits ever
stand whole in float32: attention runs in blocks of ``QUERY_BLOCK`` queries.
``mode="fp8"`` is the control (every matmul operand rounded to e4m3, as
``reference.py`` does it); ``mode="no_window"`` is the window's control (a
window layer attends causally over the whole row); neither decides ``correct``.

One layer, x [T, d] (every size as published: d 4096, 128 query heads and 8
key-value heads of 128, window 4096, 128 experts of width 4096, 8 a token, 4
shared experts):

    h   = LN(x) = (x - mean x) / sqrt(var x + layer_norm_eps) * g      one norm a layer
    q, k, v = h Wq [T,128,128], h Wk [T,8,128], h Wv [T,8,128]         no bias, no qk-norm
    query head j reads key-value head j // 16
    sliding_attention layer: q, k <- rotary over interleaved pairs (2i, 2i+1):
        (a, b) -> (a cos - b sin, b cos + a sin), angle t * theta^(-2i/128), theta 50,000,
        all 128 dims; allowed(t, s): 0 <= t - s < sliding_window
    full_attention layer: no rotation; allowed(t, s): s <= t
    o   = concat_heads(softmax(q k^T / sqrt(128) | allowed) v) Wo
    s   = sigmoid(h Wr) in R^128;  C = the 8 largest;  w_e = s_e / sum_{c in C} s_c
    m   = sum_{e in C, e held here} w_e E_e(h) + (1/4) sum_{j=1..4} S_j(h)
          E(h) = (silu(h Wg) * (h Wu)) Wd, 4096 -> 4096 -> 4096
    y   = x + o + m                                                    parallel block

After the last layer: LN (final gain) at the last real token, L2-normalised.

*Assumed* (the configuration file lists each): ``intermediate_size`` is the
width of one routed and of one shared expert; ``average`` is the mean of the
shared experts' outputs, added to the routed sum; the router has no
correction bias and no scaling; global layers carry no rotary ("global NoPE",
the family's convention); the window counts the token itself; the layer norm
is mean-centred with a gain and no bias; the pooling (the published model is
a generator). *The share*: this chip holds ``experts_held`` = (first, count)
of the ``published.num_experts`` the router scores; what the other chips'
experts would add is left out, here as in the program, and that partial
result goes on to the next layer. The shared experts arrive side by side as
one gated FFN of width 4 x 4096, whose output is their sum. *Left out*: the
vision tower, the output head and ``logit_scale``, decoding.

**A choice that is followed** (``reference_trunk.py`` says why): told which
experts the timed path's router chose (``forced``), the reference weighs
those by its own scores and hands back its scores of every expert, so the
vectors differ by arithmetic alone and the choice is judged apart. With a
choice to follow it also knows how many tokens the busiest held expert
takes, and each expert is applied to the tokens sent to it (``nonzero``,
padded to that count) instead of to every token; without one, to every token.

Tokens: ``reference.py``'s hashing tokenizer (CLS, then one hashed id a token).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.reference import PAD_ID, _einsum, tokenize

F32 = jnp.float32
QUERY_BLOCK = 128


def _up(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def layer_norm(x, gain, eps):
    centred = x - x.mean(axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt((centred * centred).mean(axis=-1, keepdims=True) + eps) * gain


def rotate_pairs(x, theta: float):
    """x [T, H, d]: each pair (2i, 2i+1) turned by t * theta^(-2i/d)."""
    length, _, width = x.shape
    angles = jnp.arange(length, dtype=F32)[:, None] * theta ** (-jnp.arange(0, width, 2, dtype=F32) / width)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def attention(p, h, config: dict, kind: str, mode="f32"):
    """h [T, d] -> [T, d]; ``kind`` is the layer's entry of ``layer_types``."""
    heads, kv_heads = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    width, length = int(config["head_dim"]), h.shape[0]
    windowed = kind == "sliding_attention"
    window = int(config["sliding_window"]) if windowed and mode != "no_window" else length
    einsum_mode = "f32" if mode == "no_window" else mode
    q = _einsum("td,dhe->the", h, p["wq"], einsum_mode)
    k = _einsum("td,dhe->the", h, p["wk"], einsum_mode)
    v = _einsum("td,dhe->the", h, p["wv"], einsum_mode)
    if windowed:
        q, k = rotate_pairs(q, float(config["rope_theta"])), rotate_pairs(k, float(config["rope_theta"]))
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    block = min(QUERY_BLOCK, length)
    position = jnp.arange(length)

    def one_block(start):
        queries = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        t = start + jnp.arange(block)
        allowed = (position[None, :] <= t[:, None]) & (t[:, None] - position[None, :] < window)
        logits = _einsum("qhe,khe->hqk", queries, k, einsum_mode) / np.sqrt(width)
        probs = jax.nn.softmax(jnp.where(allowed[None], logits, -jnp.inf), axis=-1)
        return _einsum("hqk,khe->qhe", probs, v, einsum_mode)

    mixed = jax.lax.map(one_block, jnp.arange(0, length, block)).reshape(length, heads, width)
    return _einsum("the,hed->td", mixed, p["wo"], einsum_mode)


def gated_ffn(p, h, mode="f32"):
    gate = _einsum("td,df->tf", h, p["w_gate"], mode)
    up = _einsum("td,df->tf", h, p["w_up"], mode)
    return _einsum("tf,fd->td", jax.nn.silu(gate) * up, p["w_down"], mode)


def router_weights(p, h, config: dict, mode="f32", forced=None):
    """Each token's weight for each expert [T, E] (zero where it was not sent
    there), which experts it was sent to [T, E], and the scores [T, E] the
    choice is made from. ``forced`` [T, k] names the experts to follow in
    place of the k largest (-1: none)."""
    k = int(config["num_experts_per_tok"])
    scores = jax.nn.sigmoid(_einsum("td,de->te", h, p["router"], mode))
    if forced is None:
        chosen = scores >= jnp.sort(scores, axis=-1)[:, -k][:, None]
    else:
        chosen = (forced[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(axis=1)
    picked = jnp.where(chosen, scores, 0.0)
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return picked, chosen, scores


def expert_ffn(p, h, config: dict, mode="f32", experts_held=None, shared=True, forced=None, busiest=None):
    """h [T, d] -> ([T, d], scores [T, E]). ``experts_held=(first, count)``
    keeps the routed part of those experts only (``p``'s expert weights are
    theirs); ``shared=False`` leaves the shared experts out. ``busiest``: no
    held expert takes more tokens than that (each is then applied to its own
    tokens only); None applies each to every token."""
    mode = "f32" if mode == "no_window" else mode
    weights, chosen, scores = router_weights(p, h, config, mode, forced)
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
        together = gated_ffn(_up(p["shared"]), h, mode)  # side by side: their sum
        if config.get("shared_expert_combination_strategy") == "average":
            together = together / int(config["num_shared_experts"])
        total = total + together
    return total, scores


def layer(p, x, forced, config: dict, kind: str, mode="f32", busiest=None):
    """One layer on x [T, d]; ``p`` as the program holds it; ``forced`` [T, k]
    or None. Returns the new x and the router's scores [T, E]."""
    h = layer_norm(x, p["norm"].astype(F32), float(config["layer_norm_eps"]))
    o = attention(_up(p["attn"]), h, config, kind, mode)
    ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(F32))
    held = config.get("experts_held")
    m, scores = expert_ffn(
        ffn, h, config, mode, experts_held=tuple(held) if held else None, forced=forced, busiest=busiest
    )
    return x + o + m, scores


def pool(x, final_norm, last, eps):
    pooled = layer_norm(x[last], final_norm.astype(F32), eps)
    return pooled / (jnp.linalg.norm(pooled) + 1e-12)


@functools.lru_cache(maxsize=32)
def _programs(config_json: str, kind: str, mode: str, busiest):
    config = json.loads(config_json)
    return (
        jax.jit(functools.partial(layer, config=config, kind=kind, mode=mode, busiest=busiest)),
        jax.jit(functools.partial(pool, eps=float(config["layer_norm_eps"]))),
    )


def encode(params, ids, length: int, config: dict, mode="f32", forced=None):
    """ids [T] of one text, right-padded, ``length`` of them real -> (unit
    vector [d], the layers' router scores [layers, T, E]). ``forced`` [layers,
    T, k]: the experts each token follows (-1: none)."""
    kinds = list(config["layer_types"])[: int(config["num_hidden_layers"])]
    busiest = None
    if forced is not None:
        first, count = config.get("experts_held") or (0, int(config["num_experts"]))
        sent = np.asarray(forced) - first
        most = max(int(np.bincount(layer[(layer >= 0) & (layer < count)], minlength=1).max()) for layer in sent)
        busiest = min(len(ids), 1 << max(most - 1, 0).bit_length())  # few distinct programs
    frozen = json.dumps(config, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)].astype(F32)
        scores = []
        for i, (kind, p) in enumerate(zip(kinds, params["layers"])):
            layer_fn, pool_fn = _programs(frozen, kind, mode, busiest)
            follow = None if forced is None else jnp.asarray(forced[i], jnp.int32)
            x, layer_scores = layer_fn(p, x, follow)
            scores.append(layer_scores)
        return pool_fn(x, params["final_norm"], max(length - 1, 0)), jnp.stack(scores)


def embed(params, texts, config: dict, *, max_len: int, mode="f32", forced=None, least=64):
    """Unit vectors [len(texts), d] of the texts and the router scores
    [layers, len(texts), max_len, E] (NaN where a text has no token), one
    text at a time, padded to the power of two that holds it, ``least`` or
    more. ``forced`` [layers, len(texts), positions, k]: the experts to
    follow, as ``encode`` takes them."""
    layers, experts = int(config["num_hidden_layers"]), int(config["published"]["num_experts"])
    vectors = np.zeros((len(texts), int(config["hidden_size"])), np.float32)
    scores = np.full((layers, len(texts), max_len, experts), np.nan, np.float32)
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
        vector, text_scores = encode(params, ids, len(encoded), config, mode, follow)
        vectors[n] = np.asarray(vector)
        real = min(len(encoded), max_len)
        scores[:, n, :real] = np.asarray(text_scores)[:, :real]
    return vectors, scores
