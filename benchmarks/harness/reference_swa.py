"""The plain reference of the window-with-sinks configurations: MiMo-V2.5
(``model_type`` ``mimo_v2``) as an embedder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernel, no key blocks, no sort, no cache, no pad ladder, nothing imported
from the program. The parameters arrive as the program holds them
(bfloat16); a layer's are upcast inside that layer's function, one routed
expert at a time, and the texts go through one at a time. Attention runs in
blocks of ``QUERY_BLOCK`` queries; a window layer's block reads the
``sliding_window`` keys before it and its own, a full layer's the whole row,
and the sink is one more logit of the row with a value of zero.
``mode="fp8"`` is the control (every matmul operand rounded to e4m3, as
``reference.py`` does it); ``mode="no_sink"`` is the sinks' control (float32,
every sink taken out of its softmax); neither decides ``correct``.

Every size as published: d 4096; 64 query heads of 192; window layers 8
key-value heads of 192 / 128, full layers 4; 256 experts of width 2048, 8 a
token, no shared expert; a dense FFN of 16,384 in layer 0. With ``norm(x) =
x / rms(x) * w`` (eps 1e-5):

    x <- x + attn_i(norm x);   x <- x + ffn_i(norm x)

    attn, window layer (hybrid_layer_pattern 1):
        q, k, v = h Wq [T,64,192], h Wk [T,8,192], h Wv [T,8,128]         no bias
        q, k <- rotate-half on dims 0..63 (i with i + 32), theta 1e4; dims 64..191 unrotated
        query head j reads key-value head j // 8;  allowed(t, s): 0 <= t - s < 128
        l_ts = q_t . k_s / sqrt(192);  p_ts = exp(l_ts) / (exp(s_j) + sum_{s' allowed} exp(l_ts'))
        attn = 0.707 concat_heads(sum_s p_ts v_s) Wo                     Wo [64 x 128, 4096]
    attn, full layer (0): 4 key-value heads (j // 16), theta 1e7, allowed(t, s): s <= t, no sink
    ffn, layer 0 (moe_layer_freq 0): (silu(h Wg) * (h Wu)) Wd, 4096 -> 16,384 -> 4096
    ffn, expert layer (1):
        s = sigmoid(h Wr) in R^256;  C = the 8 largest of s + b;  w_e = s_e / sum_{c in C} s_c
        ffn = sum_{e in C, e held here} w_e E_e(h);   E(h) = (silu(h Wg) * (h Wu)) Wd, 4096 -> 2048 -> 4096

After the last layer: ``norm`` (final gain) at the last real token,
L2-normalised.

*Assumed* (the configuration file lists each): the layout of the
projections; the window as ``sliding_window``; the rotate-half pairing; the
softmax scale; the pooling (the published model is a generator). *The
share*: this chip holds ``experts_held`` = (first, count) of the
``published.n_routed_experts`` the router scores; what the other chips'
experts would add is left out, here as in the program, and that partial
result goes on to the next layer. *Left out*: the output head, multi-token
prediction, decoding, the vision and audio towers.

The router is ``reference_trunk``'s (the sigmoid, the correction bias, the
top 8 of the corrected scores, the weights normalised), the null
``routed_scaling_factor`` read as 1; each expert is applied to its own
tokens as ``reference_gqa.expert_ffn`` does. **A choice that is followed**
(``reference_trunk.py`` says why): told which experts the timed path's
router chose, the reference weighs those by its own scores and hands back its
corrected scores of every expert.

Tokens: ``reference.py``'s hashing tokenizer (CLS, then one hashed id a token).
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference_gqa, reference_trunk
from benchmarks.harness.reference import PAD_ID, _einsum, tokenize
from benchmarks.harness.reference_gdn import rotate_half
from benchmarks.harness.reference_gqa import _up, gated_ffn
from benchmarks.harness.reference_ssm import QUERY_BLOCK, rms_norm

F32 = jnp.float32


def _arithmetic(mode: str) -> str:
    """The matmuls' mode: the sinks' control is float32 arithmetic."""
    return "f32" if mode == "no_sink" else mode


def layer_kinds(config: dict) -> list[str]:
    """Each layer's attention: ``"window"`` (pattern 1) or ``"full"`` (0)."""
    pattern = list(config["hybrid_layer_pattern"])[: int(config["num_hidden_layers"])]
    return ["window" if kind == 1 else "full" for kind in pattern]


def sparse_layers(config: dict) -> list[bool]:
    """Whether each layer has experts (``moe_layer_freq`` 1)."""
    return [freq == 1 for freq in list(config["moe_layer_freq"])[: int(config["num_hidden_layers"])]]


def router_weights(p, h, config: dict, mode="f32", forced=None):
    """``reference_trunk.router_weights`` in ``reference_gqa``'s form: each
    token's weight for each expert [T, E], which experts it was sent to, and
    the corrected scores the choice is made from."""
    scaling = config.get("routed_scaling_factor") or 1.0
    weights, corrected = reference_trunk.router_weights(p, h, dict(config, routed_scaling_factor=scaling), mode, forced)
    return weights, weights > 0, corrected


expert_ffn = types.FunctionType(
    reference_gqa.expert_ffn.__code__, {**vars(reference_gqa), "router_weights": router_weights}, "expert_ffn",
    reference_gqa.expert_ffn.__defaults__,
)


def attention(p, h, config: dict, kind: str, mode="f32"):
    """h [T, d] -> [T, d]; ``kind`` ``"window"`` or ``"full"``."""
    window = kind == "window"
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["swa_num_key_value_heads" if window else "num_key_value_heads"])
    width, length, arithmetic = int(config["head_dim"]), h.shape[0], _arithmetic(mode)
    theta = float(config["swa_rope_theta" if window else "rope_theta"])
    dims = int(width * float(config["partial_rotary_factor"]))
    q = rotate_half(_einsum("td,dhe->the", h, p["wq"], arithmetic), theta, dims)
    k = rotate_half(_einsum("td,dhe->the", h, p["wk"], arithmetic), theta, dims)
    v = _einsum("td,dhe->the", h, p["wv"], arithmetic)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    block = min(QUERY_BLOCK, length)
    seen = int(config["sliding_window"])
    if window:  # zeros before the row, masked: a block reads the ``seen`` keys before it and its own
        k, v = (jnp.pad(a, ((seen, 0), (0, 0), (0, 0))) for a in (k, v))
    sinks = p["sinks"] if window and mode != "no_sink" else None

    def one_block(start):
        queries = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        t = start + jnp.arange(block)[:, None]
        if window:
            keys, values = (jax.lax.dynamic_slice_in_dim(a, start, block + seen, axis=0) for a in (k, v))
            s = start - seen + jnp.arange(block + seen)[None, :]
            allowed = (s >= 0) & (s <= t) & (t - s < seen)
        else:
            keys, values, s = k, v, jnp.arange(length)[None, :]
            allowed = s <= t
        logits = _einsum("qhe,khe->hqk", queries, keys, arithmetic) / np.sqrt(width)
        logits = jnp.where(allowed[None], logits, -jnp.inf)
        if sinks is not None:  # one more logit a row, whose value is zero
            logits = jnp.concatenate([logits, jnp.broadcast_to(sinks[:, None, None], logits.shape[:2] + (1,))], -1)
        probs = jax.nn.softmax(logits, axis=-1)[..., : keys.shape[0]]
        return _einsum("hqk,khe->qhe", probs, values, arithmetic)

    mixed = jax.lax.map(one_block, jnp.arange(0, length, block)).reshape(length, heads, v.shape[-1])
    return float(config["attention_value_scale"]) * _einsum("the,hed->td", mixed, p["wo"], arithmetic)


def layer(p, x, forced, config: dict, kind: str, sparse: bool, mode="f32", busiest=None):
    """One layer on x [T, d]; ``p`` as the program holds it; ``forced`` [T, k]
    or None. Returns the new x and, for an expert layer, the router's
    corrected scores [T, E] (None for the dense one)."""
    eps, arithmetic = float(config["layernorm_epsilon"]), _arithmetic(mode)
    x = x + attention(_up(p["attn"]), rms_norm(x, p["attn_norm"].astype(F32), eps), config, kind, mode)
    h = rms_norm(x, p["ffn_norm"].astype(F32), eps)
    if not sparse:
        return x + gated_ffn(_up(p["ffn"]), h, arithmetic), None
    ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(F32), bias=p["ffn"]["bias"].astype(F32))
    held = config.get("experts_held")
    routed, corrected = expert_ffn(
        ffn, h, config, arithmetic, experts_held=tuple(held) if held else None, shared=False, forced=forced,
        busiest=busiest,
    )
    return x + routed, corrected


def pool(x, final_norm, last, eps):
    pooled = rms_norm(x[last], final_norm.astype(F32), eps)
    return pooled / (jnp.linalg.norm(pooled) + 1e-12)


@functools.lru_cache(maxsize=64)
def _programs(config_json: str, kind: str, sparse: bool, mode: str, busiest):
    config = json.loads(config_json)
    return (
        jax.jit(functools.partial(layer, config=config, kind=kind, sparse=sparse, mode=mode, busiest=busiest)),
        jax.jit(functools.partial(pool, eps=float(config["layernorm_epsilon"]))),
    )


def routed_experts(config: dict) -> int:
    """The router's width: the published count where the file is a share's."""
    return int(config.get("published", {}).get("n_routed_experts", config["n_routed_experts"]))


def encode(params, ids, length: int, config: dict, mode="f32", forced=None):
    """ids [T] of one text, right-padded, ``length`` of them real -> (unit
    vector [d], the expert layers' corrected router scores [expert layers, T,
    E]). ``forced`` [expert layers, T, k]: the experts each token follows (-1:
    none)."""
    busiest = None
    if forced is not None:
        first, count = config.get("experts_held") or (0, routed_experts(config))
        sent = np.asarray(forced) - first
        most = max(int(np.bincount(layer[(layer >= 0) & (layer < count)], minlength=1).max()) for layer in sent)
        # an eighth of the row at the least, four times a held expert's mean share: one program a width
        busiest = min(len(ids), max(1 << max(most - 1, 0).bit_length(), len(ids) // 8))
    frozen = json.dumps(config, sort_keys=True)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(ids)].astype(F32)
        scores = []
        for kind, sparse, p in zip(layer_kinds(config), sparse_layers(config), params["layers"]):
            if ("router" in p["ffn"]) != sparse:
                raise ValueError("the parameters and the configuration's moe_layer_freq disagree")
            layer_fn, pool_fn = _programs(frozen, kind, sparse, mode, busiest if sparse else None)
            follow = jnp.asarray(forced[len(scores)], jnp.int32) if sparse and forced is not None else None
            x, corrected = layer_fn(p, x, follow)
            if sparse:
                scores.append(corrected)
        return pool_fn(x, params["final_norm"], max(length - 1, 0)), jnp.stack(scores)


def embed(params, texts, config: dict, *, max_len: int, mode="f32", forced=None, least=64):
    """Unit vectors [len(texts), d] of the texts and the corrected router
    scores [expert layers, len(texts), max_len, E] float16 (NaN where a text
    has no token), one text at a time, padded to the power of two that holds
    it, ``least`` or more. ``forced`` [expert layers, len(texts), positions,
    k]: the experts to follow, as ``encode`` takes them."""
    layers, experts = sum(sparse_layers(config)), routed_experts(config)
    vectors = np.zeros((len(texts), int(config["hidden_size"])), np.float32)
    # float16 on the host: 256 scores a position and layer over dozens of texts of 16,384 positions
    scores = np.full((layers, len(texts), max_len, experts), np.nan, np.float16)
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
