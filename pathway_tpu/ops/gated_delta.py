"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), chunk by chunk.

For each value head, with a state ``S`` [d_k, d_v] float32 that starts at
zero, ``alpha_t = exp(g_t)`` and ``beta_t`` in (0, 1)::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T       o_t = S_t^T q_t

``scan(q, k, v, g, beta)`` takes ``q``, ``k`` [B, T, H_k, d_k] (normalised
and scaled by the caller), ``v`` [B, T, H_v, d_v], the log-decays ``g`` [B,
T, H_v] (<= 0) and ``beta`` [B, T, H_v], and returns ``o`` [B, T, H_v, d_v].
Value head ``j`` reads key head ``j // (H_v / H_k)``; ``q`` and ``k`` are
never repeated. In chunks of ``C`` positions, with ``G`` the running sums of
``g`` inside the chunk and ``H`` the state carried into it (the WY form)::

    A = strictly_lower(diag(beta) (K K^T * exp(G_i - G_j)))
    T = (I + A)^-1                                   a unit lower-triangular inverse
    W = T diag(beta exp(G)) K          U = T diag(beta) V
    O = diag(exp(G)) Q H + lower(Q K^T * exp(G_i - G_j)) (U - W H)
    H <- exp(G_C) H + (K * exp(G_C - G))^T (U - W H)

``T`` is taken by blocks (``unit_lower_inverse``): the diagonal blocks of 4
as the whole series ``(I + N)(I + N^2)`` with ``N = -A``, then pairs of
blocks merged level by level, two matmuls a level: ten matmuls of [C, C] at
``C = 64``, and no term larger than the inverse's own entries.

One Pallas kernel (its device ops are called ``GDN_KERNEL_NAME`` in a trace):
the grid walks a row's chunks in order, innermost and sequential, for a block
of ``HEAD_BLOCK`` value heads and the key heads they read. Per chunk, in VMEM
only: the running sums (a matmul with a triangle of ones, so that they come
both as a column and as a row without a transpose), ``K K^T`` and ``Q K^T``
once a key head, the decay mask, ``T``, ``W``, ``U``, the output and the
state's update; the block's states [heads, d_k, d_v] float32 are carried in
scratch. Decays, exponentials, ``beta``, ``T``, ``W``, ``U`` and the state
are float32; ``q``, ``k``, ``v`` and the delta ``U - W H`` enter the matmuls
in ``v``'s dtype and accumulate in float32. No array of [B, T / C, H, C, C]
ever reaches HBM.

Right padding needs no mask: the recurrence is causal and the trunk pools at
the last real token. A length that is not a whole number of chunks is padded
with ``beta = 0``, ``g = 0``: the state passes through unchanged.

``scan_xla`` is the plain-XLA twin of the same chunked form (a ``lax.scan``
over chunks), what runs where Mosaic does not (``ops/backend.py`` decides).
``chunks_useful`` and ``chunks_visited`` count the chunks that hold a real
token and the chunks walked at the forwarded shape, as ``ssd_scan``'s do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.backend import pallas_interpret
from pathway_tpu.ops.ssd_scan import chunks_useful as _chunks_useful
from pathway_tpu.ops.ssd_scan import chunks_visited as _chunks_visited

GDN_KERNEL_NAME = "gated_delta_chunk_scan"
CHUNK = 64  # the family's chunk (its modelling code's chunked form)
HEAD_BLOCK = 8  # value heads a grid step works through
DIAGONAL_BLOCK = 4  # of the triangular inverse's first step
_VMEM_LIMIT = 64 * 1024 * 1024
_HIGHEST = jax.lax.Precision.HIGHEST


def chunks_useful(lengths, chunk: int = CHUNK) -> int:
    """Chunks that hold a real token, over rows of ``lengths`` real tokens (one layer)."""
    return _chunks_useful(lengths, chunk)


def chunks_visited(rows: int, width: int, chunk: int = CHUNK) -> int:
    """Chunks the scan walks for ``rows`` rows forwarded at ``width`` positions (one layer)."""
    return _chunks_visited(rows, width, chunk)


def unit_lower_inverse(a, dot):
    """``(I + a)^-1`` of a strictly lower ``a`` [..., C, C] float32, by
    blocks: the diagonal blocks of ``DIAGONAL_BLOCK`` first, each the product
    ``(I + N)(I + N^2)`` with ``N = -a`` (the whole series: ``N^4 = 0``
    inside a block of 4), then pairs of blocks merged, level by level, as
    ``[[T11, 0], [-T22 a21 T11, T22]]``. The series alone over all of ``C``
    would sum terms that grow like ``C(C - 1, n)`` where the keys of a chunk
    are alike (the padding after a row's last token: one token repeated) and
    cancel them to entries of order one: float32 cannot, and the chunk comes
    out as garbage. ``dot`` is the batched matmul."""
    size = a.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    eye = (row == col).astype(jnp.float32)
    block = min(DIAGONAL_BLOCK, size)
    power = jnp.where(row // block == col // block, -a, 0.0)
    inverse = eye + power
    for _ in range(max(math.ceil(math.log2(block)), 1) - 1):
        power = dot(power, power)
        inverse = inverse + dot(inverse, power)
    while block < size:  # the blocks of 2 x ``block`` whose first half of the columns lies below their second half of the rows
        below = (row // (2 * block) == col // (2 * block)) & ((row // block) % 2 == 1) & ((col // block) % 2 == 0)
        inverse = inverse - dot(inverse, dot(jnp.where(below, a, 0.0), inverse))
        block *= 2
    return inverse


def _padded(q, k, v, g, beta, chunk: int):
    pad = -v.shape[1] % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    return q, k, v, g, beta


def scan_xla(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked form in plain XLA, one chunk of every row and head at a
    time; roundings as the kernel's."""
    batch, length, key_heads, dk = q.shape
    heads, dv = v.shape[2], v.shape[3]
    group, dtype = heads // key_heads, v.dtype
    chunk = min(chunk, length)
    q, k, v, g, beta = _padded(q, k, v, g, beta, chunk)
    n = v.shape[1] // chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def dot(a, b, precision=_HIGHEST):
        return jnp.matmul(a, b, precision=precision, preferred_element_type=jnp.float32)

    def chunked(a):  # [B, n C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(a.reshape((batch, n, chunk) + a.shape[2:]), 1, 0)

    def step(state, part):  # state [B, H_k, group, d_k, d_v] float32
        qc, kc, vc, gc, bc = part
        by_head = lambda a: a.reshape(batch, chunk, key_heads, group, -1).transpose(0, 2, 3, 1, 4)
        at = by_head(jnp.cumsum(gc.astype(jnp.float32), axis=1))  # [B, H_k, group, C, 1]: G as a column
        b = by_head(bc.astype(jnp.float32))
        q_heads, k_heads = (a.transpose(0, 2, 1, 3)[:, :, None] for a in (qc, kc))  # [B, H_k, 1, C, d_k]
        kk = dot(k_heads, jnp.swapaxes(k_heads, -1, -2), None)  # [B, H_k, 1, C, C]
        qk = dot(q_heads, jnp.swapaxes(k_heads, -1, -2), None)
        decay = jnp.exp(jnp.minimum(at - jnp.swapaxes(at, -1, -2), 0.0))  # [B, H_k, group, C, C]
        t = unit_lower_inverse(jnp.where(strict, b * kk * decay, 0.0), dot)
        k32, grown = k_heads.astype(jnp.float32), jnp.exp(at)
        w = dot(t, b * grown * k32)  # [B, H_k, group, C, d_k]
        u = dot(t, b * by_head(vc).astype(jnp.float32))
        delta = (u - dot(w.astype(dtype), state.astype(dtype), None)).astype(dtype)
        inside = jnp.where(lower, qk * decay, 0.0).astype(dtype)
        out = grown * dot(q_heads, state.astype(dtype), None) + dot(inside, delta, None)
        last = at[..., -1:, :]  # [B, H_k, group, 1, 1]: G_C
        faded = (k32 * jnp.exp(last - at)).astype(dtype)  # K * exp(G_C - G)
        state = jnp.exp(last) * state + dot(jnp.swapaxes(faded, -1, -2), delta, None)
        out = out.transpose(0, 3, 1, 2, 4).reshape(batch, chunk, heads, dv)
        return state, out.astype(dtype)

    first = jnp.zeros((batch, key_heads, group, dk, dv), jnp.float32)
    _, o = jax.lax.scan(step, first, tuple(chunked(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(batch, n * chunk, heads, dv)[:, :length]


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref, *, heads, group, dk, dv, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    def dot(a, b):
        return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)

    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower, strict = r <= t, r < t
    g_col, beta_col = g_ref[0, 0], beta_ref[0, 0]  # [C, heads] float32
    running = dot(lower.astype(jnp.float32), g_col)  # G, a column a head: [C, heads]
    ones = jnp.ones((chunk, chunk), jnp.float32)
    dtype = v_ref.dtype
    for key in range(heads // group):
        q = q_ref[0, :, key * dk : (key + 1) * dk]  # [C, d_k]
        k = k_ref[0, :, key * dk : (key + 1) * dk]
        k32 = k.astype(jnp.float32)
        kk = jax.lax.dot_general(k, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        for h in range(key * group, (key + 1) * group):
            col = running[:, h : h + 1]  # [C, 1]: G_i
            row = dot(ones, g_col[:, h : h + 1] * (t <= r).astype(jnp.float32))  # [C, C]: G_j on every row
            # above the diagonal G_i - G_j is positive and masked out: the minimum keeps the exponential finite
            decay = jnp.exp(jnp.minimum(col - row, 0.0))
            b = beta_col[:, h : h + 1]
            inverse = unit_lower_inverse(jnp.where(strict, b * kk * decay, 0.0), dot)
            grown = jnp.exp(col)
            w = dot(inverse, b * grown * k32)  # [C, d_k]
            at = slice(h * dv, (h + 1) * dv)
            u = dot(inverse, b * v_ref[0, :, at].astype(jnp.float32))  # [C, d_v]
            state = state_ref[h]  # [d_k, d_v]
            delta = (u - jnp.dot(w.astype(dtype), state.astype(dtype), preferred_element_type=jnp.float32)).astype(dtype)
            inside = jnp.where(lower, qk * decay, 0.0).astype(dtype)
            out = grown * jnp.dot(q, state.astype(dtype), preferred_element_type=jnp.float32)
            out += jnp.dot(inside, delta, preferred_element_type=jnp.float32)
            o_ref[0, :, at] = out.astype(o_ref.dtype)
            # G_C as a column and as a row, each from a matmul: a [1, 1] slice broadcast over
            # sublanes and lanes at once is what Mosaic refuses
            faded = (k32 * jnp.exp(row[:, chunk - 1 :] - col)).T.astype(dtype)  # [d_k, C]: K * exp(G_C - G)
            total = dot(jnp.ones((8, chunk), jnp.float32), g_col[:, h : h + 1] * jnp.ones((chunk, dv), jnp.float32))
            state_ref[h] = jnp.exp(total[:1]) * state + jnp.dot(faded, delta, preferred_element_type=jnp.float32)


def scan_pallas(q, k, v, g, beta, chunk: int = CHUNK):
    """The kernel. ``q``, ``k`` and ``v`` stay as they lie ([B, T, H d]:
    heads side by side on the lanes; a block of value heads reads the lanes
    of its key heads); ``g`` and ``beta`` arrive with positions on the
    sublanes and a block's heads on the lanes."""
    batch, length, key_heads, dk = q.shape
    heads, dv = v.shape[2], v.shape[3]
    group = heads // key_heads
    chunk = min(chunk, length)
    head_block = min(HEAD_BLOCK, heads)
    if heads % head_block or head_block % group:
        raise ValueError(f"gated_delta: {heads} value heads are no whole number of blocks of {head_block} over groups of {group}")
    q, k, v, g, beta = _padded(q, k, v, g, beta, chunk)
    padded = v.shape[1]
    blocks, keys = heads // head_block, head_block // group

    def columns(a):  # [B, T, H] -> [B, blocks, T, head_block]
        return a.astype(jnp.float32).reshape(batch, padded, blocks, head_block).transpose(0, 2, 1, 3)

    o = pl.pallas_call(
        functools.partial(_kernel, heads=head_block, group=group, dk=dk, dv=dv, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((batch, padded, heads * dv), v.dtype),
        grid=(batch, blocks, padded // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, keys * dk), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, chunk, keys * dk), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, chunk, head_block * dv), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, 1, chunk, head_block), lambda b, j, i: (b, j, i, 0)),
            pl.BlockSpec((1, 1, chunk, head_block), lambda b, j, i: (b, j, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, head_block * dv), lambda b, j, i: (b, i, j)),
        scratch_shapes=[pltpu.VMEM((head_block, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=pallas_interpret(),
        name=GDN_KERNEL_NAME,
    )(
        q.reshape(batch, padded, key_heads * dk), k.reshape(batch, padded, key_heads * dk),
        v.reshape(batch, padded, heads * dv), columns(g), columns(beta),
    )
    return o.reshape(batch, padded, heads, dv)[:, :length]


def scan(q, k, v, g, beta, chunk: int = CHUNK):
    """``o`` [B, T, H_v, d_v] of the recurrence above: the kernel on a TPU,
    its XLA twin elsewhere."""
    if pallas_interpret():
        return scan_xla(q, k, v, g, beta, chunk)
    return scan_pallas(q, k, v, g, beta, chunk)
