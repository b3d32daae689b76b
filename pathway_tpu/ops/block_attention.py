"""Causal grouped-query attention over a right-padded batch, block by block.

``attention(q, k, v)`` takes the query heads grouped by the key-value head
they read (``q`` [B, H_kv, G, T, d]; ``k`` [B, H_kv, T, d]; ``v`` [B, H_kv,
T, d_v]: query head ``j`` of the model is ``q[:, j // G, j % G]``) and
returns ``softmax(q k^T scale | allowed) v`` in ``q``'s layout at ``v``'s
width (``d_v`` may be narrower than ``d``). ``allowed(t, s)`` is ``s <= t``,
and with ``window=W`` also ``t - s < W`` (the window counts the token
itself). ``sinks`` [H_kv, G] float32 gives each query head a **sink**: a
logit ``s_h`` with no value, ``p_ts = exp(l_ts) / (exp(s_h) + sum_s'
exp(l_ts'))``, which takes probability mass from a row's keys and adds
nothing. It costs no pass of its own: a row's running maximum starts at
``s_h`` and its normaliser at 1, so a row whose first blocks are all masked
starts from a finite maximum as well.

One Pallas kernel, flash-style: a grid step holds the ``G`` query heads of
one key-value head over ``block_q`` positions (``G * block_q`` rows) against
one block of ``block_k`` keys; the running maximum, the normaliser and the
float32 accumulator live in VMEM scratch, so no logit ever reaches HBM and a
key-value head is read once for its ``G`` query heads, not expanded to them.
A query block visits only the key blocks that hold an allowed pair
(``kv_range``): the grid's last axis is as long as the widest such range, and
its steps past a block's own range are skipped and mapped onto the last block
they used, so nothing is moved for them. Blocks that lie wholly inside the
mask skip the mask arithmetic too. A window under ``BLOCK_K`` keys brings
the key block down to ``WINDOW_BLOCK_K``: at window 128 a query block of 128
then visits two key blocks of 128, half of whose pairs are allowed, where
blocks of 512 would visit one or two for an eighth to a quarter. A window of
``BLOCK_K`` or more, or none, keeps ``BLOCK_K``.

Right padding needs no mask of its own: a padding key lies after every real
query, so the causal mask already hides it. ``lengths`` [B] says how many
real tokens each row holds, and the kernel reads it as a scalar prefetch: a
query block whose first position is at or past its row's length is **dead**.
A dead block runs no matmul and no softmax on any of its steps, moves
nothing (its maps stay on the blocks the row's last live step used) and
writes **zeros**: nobody reads a padding query's output (the trunk pools at
the last real token), but it goes on through the out-projection, so it has
to be finite, and the same on every call. The padding positions of a row's
last live block hold finite numbers nobody reads, as before. A live block is
computed as it would be without ``lengths``, to the last bit. Multiplies in
the operands' dtype, logits, softmax and accumulation in float32. Its device
ops are called ``ATTN_KERNEL_NAME`` in a trace; ``ops/backend.py`` decides
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.backend import pallas_interpret

ATTN_KERNEL_NAME = "block_causal_attention"
BLOCK_Q = 128  # query positions a step; times G heads they are the rows of both matmuls
BLOCK_K = 512  # keys a step
WINDOW_BLOCK_K = 128  # keys a step for a window under BLOCK_K (a v5e sweep of 128, 256 and 512: PERF.md)
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)  # a row's first blocks may be all masked: see _step
_VMEM_LIMIT = 64 * 1024 * 1024


def window_block(window: int | None) -> int:
    """The key block at ``window``: ``WINDOW_BLOCK_K`` under ``BLOCK_K``, else ``BLOCK_K``."""
    return WINDOW_BLOCK_K if window is not None and window < BLOCK_K else BLOCK_K


def blocks(
    length: int, block_q: int | None = None, block_k: int | None = None, window: int | None = None
) -> tuple[int, int]:
    """The block sizes ``attention`` uses at ``length`` positions and ``window``."""
    return min(block_q or BLOCK_Q, length), min(block_k or window_block(window), length)


def kv_range(qi, block_q: int, block_k: int, window: int | None):
    """First and last key block that hold a pair allowed to query block ``qi``."""
    first_query, last_query = qi * block_q, qi * block_q + block_q - 1
    largest = max if isinstance(qi, int) else jnp.maximum
    lo = 0 if window is None else largest(first_query - (window - 1), 0) // block_k
    return lo, last_query // block_k


def visited_steps(
    length: int, window: int | None, block_q: int, block_k: int, tokens: int | None = None
) -> list[int]:
    """Key blocks each query block of a ``length``-position row visits when
    the row holds ``tokens`` real tokens (``None``: all of them are): none
    for a dead block."""
    tokens = length if tokens is None else tokens
    steps = []
    for qi in range(-(-length // block_q)):
        lo, hi = kv_range(qi, block_q, block_k, window)
        steps.append(hi - lo + 1 if qi * block_q < tokens else 0)
    return steps


def pairs_visited(
    length: int, window: int | None = None, block_q=None, block_k=None, tokens: int | None = None
) -> int:
    """Query-key pairs of the blocks the kernel visits for one row of
    ``length`` positions (one head) that holds ``tokens`` real tokens
    (``None``: all): what it computes, the padding inside the last live block
    and the masked corners of its edge blocks included."""
    block_q, block_k = blocks(length, block_q, block_k, window)
    return sum(visited_steps(length, window, block_q, block_k, tokens)) * block_q * block_k


def pairs_allowed(tokens: int, window: int | None = None) -> int:
    """Query-key pairs inside the mask for a row of ``tokens`` real tokens."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def _last_live(lengths_ref, b, block_q: int):
    """The last query block of row ``b`` that holds a real token (0 for an empty row)."""
    return jnp.maximum((lengths_ref[b] + block_q - 1) // block_q - 1, 0)


def _kernel(lengths_ref, *refs, scale, window, block_q, block_k, sinks: bool):
    if sinks:
        q_ref, k_ref, v_ref, sink_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads, _, width = q_ref.shape[2:]
    rows = heads * block_q
    lo, hi = kv_range(qi, block_q, block_k, window)
    kb = lo + j
    live = qi * block_q < lengths_ref[b]
    last_step = j == pl.num_programs(3) - 1

    @pl.when(live & (j == 0))
    def _():
        if sinks:  # the sink's own term: exp(s_h - m) = 1 at m = s_h, and no value
            m_ref[...] = sink_ref[0]
            l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        else:
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _step(masked: bool):
        q = q_ref[0, 0].reshape(rows, width)
        k, v = k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, block_k]
        if masked:
            # row g * block_q + i is position qi * block_q + i of head g
            s = s.reshape(heads, block_q, block_k)
            t = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            at = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            allowed = at <= t
            if window is not None:
                allowed &= t - at < window
            s = jnp.where(allowed, s, _MASKED).reshape(rows, block_k)
        # A row whose pairs in this block are all masked takes the mask value
        # as its maximum and counts every key once; its own diagonal block
        # comes later, and exp(_MASKED - a real maximum) = 0 wipes that out.
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    # wholly inside the mask: the block's last key is no later than the query
    # block's first position, and its first key within the window of the last
    inside = kb * block_k + block_k - 1 <= qi * block_q
    if window is not None:
        inside &= qi * block_q + block_q - 1 - kb * block_k < window
    visited = live & (kb <= hi)
    pl.when(visited & inside)(functools.partial(_step, False))
    pl.when(visited & jnp.logical_not(inside))(functools.partial(_step, True))

    @pl.when(live & last_step)
    def _():
        out = acc_ref[...] / l_ref[...]
        o_ref[0, 0] = out.reshape(heads, block_q, o_ref.shape[-1]).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live) & last_step)
    def _():
        o_ref[0, 0] = jnp.zeros(o_ref.shape[2:], o_ref.dtype)


def attention(
    q, k, v, *, scale: float, window: int | None = None, lengths=None, sinks=None, block_q=None, block_k=None
):
    """``q`` [B, H_kv, G, T, d]; ``k`` [B, H_kv, T, d]; ``v`` [B, H_kv, T,
    d_v] -> [B, H_kv, G, T, d_v]. ``lengths`` int32 [B]: the real tokens of
    each right-padded row (``None``: every row is full); the positions of a
    row's dead blocks come back zero. ``sinks`` float32 [H_kv, G]: a sink a
    query head (``None``: none)."""
    batch, kv_heads, group, length, width = q.shape
    width_v = v.shape[-1]
    lengths = jnp.full((batch,), length, jnp.int32) if lengths is None else jnp.asarray(lengths, jnp.int32)
    block_q, block_k = blocks(length, block_q, block_k, window)
    pad = -length % max(block_q, block_k)
    if pad:  # a length off the ladder: padding keys lie after every real query
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (k, v))
    padded = length + pad
    steps = max(visited_steps(padded, window, block_q, block_k))

    # a dead block's inputs stay where the row's last live step left them
    def q_map(b, h, qi, j, lengths_ref):
        return (b, h, 0, jnp.minimum(qi, _last_live(lengths_ref, b, block_q)), 0)

    def kv_map(b, h, qi, j, lengths_ref):
        last = _last_live(lengths_ref, b, block_q)
        lo, hi = kv_range(jnp.minimum(qi, last), block_q, block_k, window)
        return (b, h, jnp.where(qi > last, hi, jnp.minimum(lo + j, hi)), 0)

    def out_map(b, h, qi, j, lengths_ref):
        return (b, h, 0, qi, 0)

    rows = group * block_q
    in_specs = [
        pl.BlockSpec((1, 1, group, block_q, width), q_map),
        pl.BlockSpec((1, 1, block_k, width), kv_map),
        pl.BlockSpec((1, 1, block_k, width_v), kv_map),
    ]
    operands = [q, k, v]
    if sinks is not None:  # row g * block_q + i of a step is query head g: its sink, a row each
        sink_rows = jnp.repeat(jnp.asarray(sinks, jnp.float32).reshape(kv_heads, group), block_q, axis=1)
        in_specs.append(pl.BlockSpec((1, rows, 1), lambda b, h, qi, j, lengths_ref: (h, 0, 0)))
        operands.append(sink_rows.reshape(kv_heads, rows, 1))
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, window=window, block_q=block_q, block_k=block_k, sinks=sinks is not None
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (width_v,), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, kv_heads, padded // block_q, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, group, block_q, width_v), out_map),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, width_v), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=pallas_interpret(),
        name=ATTN_KERNEL_NAME,
    )(lengths, *operands)
    return out[:, :, :, :length] if pad else out
