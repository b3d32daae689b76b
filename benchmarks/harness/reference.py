"""The plain reference: tokenizer, encoder forward and exact top-k.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernel, no cache, no pad ladder, and nothing imported from the program. It
follows the equations the configuration files state (``assumed``): hashing
tokenizer, pre-LN transformer blocks with a tanh-GELU 4x feed-forward, masked
mean pool, L2 normalisation; cosine top-k by brute force.

``mode="fp8"`` is the control: the same reference with every matmul's two
operands rounded to float8 (e4m3, one scale per tensor), the nearest precision
below the bf16 the configurations state. It is never used to decide
``correct``; it shows that the comparison fails a lower precision.
"""

from __future__ import annotations

import functools
import hashlib
import re
import struct

import jax
import jax.numpy as jnp
import numpy as np

_TOKEN_RE = re.compile(r"[a-zA-Z]+|\d+|[^\sa-zA-Z\d]", re.UNICODE)
PAD_ID, CLS_ID, RESERVED = 0, 1, 2
LN_EPS = 1e-6
MISSING = 2.0  # the gap charged for an answer that is not there (cosine spans 2)


def tokenize(text: str, vocab_size: int, max_len: int) -> list[int]:
    ids = [CLS_ID]
    for token in _TOKEN_RE.findall(text.lower()):
        digest = hashlib.blake2b(token.encode(), digest_size=8).digest()
        ids.append(RESERVED + struct.unpack("<Q", digest)[0] % (vocab_size - RESERVED))
    return ids[:max_len]


def _fp8(x):
    """``x`` rounded to 4 exponent and 3 mantissa bits under one scale a
    tensor. ``reduce_precision`` and not a cast there and back: the TPU
    compiler removes such a pair of casts where it can (it did in the score
    of picked rows, my chip run, PR 25), and the control would then be exact."""
    scale = jnp.max(jnp.abs(x)) / 224.0 + 1e-30
    return jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale


def _einsum(spec: str, a, b, mode: str):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown reference mode {mode!r}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _encode(params, ids, mask, depth: int, mode: str):
    p = params["params"]
    x = p["Embed_0"]["embedding"][ids] + p["Embed_1"]["embedding"][: ids.shape[1]][None]
    keep = (mask[:, None, None, :] * mask[:, None, :, None]) > 0
    for i in range(depth):
        a = p[f"MultiHeadDotProductAttention_{i}"]
        h = _layer_norm(x, p[f"LayerNorm_{2 * i}"])
        q = _einsum("btd,dhe->bthe", h, a["query"]["kernel"], mode) + a["query"]["bias"]
        k = _einsum("btd,dhe->bthe", h, a["key"]["kernel"], mode) + a["key"]["bias"]
        v = _einsum("btd,dhe->bthe", h, a["value"]["kernel"], mode) + a["value"]["bias"]
        logits = _einsum("bqhe,bkhe->bhqk", q / np.sqrt(q.shape[-1]), k, mode)
        logits = jnp.where(keep, logits, jnp.finfo(jnp.float32).min)
        mix = _einsum("bhqk,bkhe->bqhe", jax.nn.softmax(logits, axis=-1), v, mode)
        x = x + _einsum("bthe,hed->btd", mix, a["out"]["kernel"], mode) + a["out"]["bias"]
        h = _layer_norm(x, p[f"LayerNorm_{2 * i + 1}"])
        up, down = p[f"Dense_{2 * i}"], p[f"Dense_{2 * i + 1}"]
        h = jax.nn.gelu(_einsum("btd,de->bte", h, up["kernel"], mode) + up["bias"])
        x = x + _einsum("bte,ed->btd", h, down["kernel"], mode) + down["bias"]
    x = _layer_norm(x, p[f"LayerNorm_{2 * depth}"])
    pooled = (x * mask[:, :, None]).sum(1) / jnp.maximum(mask.sum(1, keepdims=True), 1.0)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-12)


_encode_jit = jax.jit(_encode, static_argnames=("depth", "mode"))


def embed(params, texts, *, vocab_size, max_len, depth, mode="f32", rows=32, least=64):
    """Unit vectors [len(texts), dim] of the texts, computed in blocks of
    ``rows`` texts, each block padded (and masked) to the power of two that
    holds its longest text, ``least`` or more: few shapes, so few programs."""
    out = []
    for start in range(0, len(texts), rows):
        encoded = [tokenize(t, vocab_size, max_len) for t in texts[start : start + rows]]
        width = max(least, 1 << (max(len(e) for e in encoded) - 1).bit_length())
        ids = np.full((rows, width), PAD_ID, dtype=np.int32)
        mask = np.zeros((rows, width), dtype=np.float32)
        for i, e in enumerate(encoded):
            ids[i, : len(e)] = e
            mask[i, : len(e)] = 1.0
        vecs = _encode_jit(params, jnp.asarray(ids), jnp.asarray(mask), depth=depth, mode=mode)
        out.append(np.asarray(vecs)[: len(encoded)])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


def _unit(x):
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-30)


@functools.partial(jax.jit, static_argnames=("mode",))
def _block_best(q, block, row_tick, q_tick, best, best_ids, offset, mode):
    """The running top-k (scores and row ids) merged with one block's."""
    s = _einsum("sd,rd->sr", _unit(q), _unit(block), mode)
    s = jnp.where(row_tick[None, :] <= q_tick[:, None], s, -jnp.inf)
    k = best.shape[1]
    block_best, block_ids = jax.lax.top_k(s, k)
    scores = jnp.concatenate([best, block_best], axis=1)
    ids = jnp.concatenate([best_ids, block_ids + offset], axis=1)
    top, pos = jax.lax.top_k(scores, k)
    return top, jnp.take_along_axis(ids, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("mode",))
def _pair_scores(q, rows, mode="f32"):
    qn, rn = _unit(q), _unit(rows)
    return _einsum("sd,skd->sk", qn, rn, mode)


def _blocks(rows_host, row_tick, block_rows):
    n = rows_host.shape[0]
    never = np.iinfo(np.int32).max
    for start in range(0, n, block_rows):
        block = rows_host[start : start + block_rows]
        ticks = row_tick[start : start + block_rows]
        short = block_rows - block.shape[0]
        if short:  # pad the last block with rows that no query may see
            block = np.concatenate([block, np.ones((short, block.shape[1]), np.float32)])
            ticks = np.concatenate([ticks, np.full(short, never, np.int32)])
        yield start, jnp.asarray(block), jnp.asarray(ticks)


def _top_k(queries, query_tick, rows_host, row_tick, k, block_rows, mode):
    q = jnp.asarray(queries, jnp.float32)
    q_tick = jnp.asarray(query_tick, jnp.int32)
    best = jnp.full((q.shape[0], k), -jnp.inf, jnp.float32)
    ids = jnp.full((q.shape[0], k), -1, jnp.int32)
    for start, block, ticks in _blocks(rows_host, row_tick, block_rows):
        best, ids = _block_best(q, block, ticks, q_tick, best, ids, start, mode=mode)
    return np.asarray(best), np.asarray(ids)


def best_scores(queries, query_tick, rows_host, row_tick, k, block_rows=131072):
    """Sorted exact top-k cosine scores [S, k] of each query over the rows it
    may see (``row_tick <= query_tick``), scanned in blocks of rows."""
    return _top_k(queries, query_tick, rows_host, row_tick, k, block_rows, "f32")[0]


def control_ids(queries, query_tick, rows_host, row_tick, k, block_rows=131072):
    """The rows the fp8 control puts first: ids [S, k] into ``rows_host``."""
    return _top_k(queries, query_tick, rows_host, row_tick, k, block_rows, "fp8")[1]


def scores_of(queries, rows_host, ids, mode="f32"):
    """Cosine score [S, k] of each query with the rows ``ids`` names, exact
    unless ``mode`` says otherwise; an id of -1 (no answer) scores ``-MISSING``."""
    ids = np.asarray(ids)
    picked = rows_host[np.maximum(ids, 0)]
    s = _pair_scores(jnp.asarray(queries, jnp.float32), jnp.asarray(picked), mode=mode)
    return np.where(ids >= 0, np.asarray(s), -MISSING)
