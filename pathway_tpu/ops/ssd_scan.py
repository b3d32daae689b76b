"""Mamba-2's selective state-space recurrence, chunk by chunk (the
state-space-dual form, arXiv:2405.21060).

For each head, with a state ``S`` [P, N] that starts at zero::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

``scan(x, dt, A, B, C, D)`` takes ``x`` [B, T, H, P], ``dt`` [B, T, H] (after
the softplus), ``A`` [H] (negative), ``B`` and ``C`` [B, T, N] (one group: all
heads share them) and ``D`` [H], and returns ``y`` [B, T, H, P]. In chunks of
``chunk`` positions, with ``s_t`` the sum of ``dt_r A`` from the chunk's
start to ``t`` and ``H`` the state carried into the chunk::

    y_t = sum_{r <= t} exp(s_t - s_r) dt_r (C_t . B_r) x_r + exp(s_t) H C_t + D x_t
    H  <- exp(s_Q) H + sum_r exp(s_Q - s_r) dt_r x_r (x) B_r

One Pallas kernel (its device ops are called ``SSD_KERNEL_NAME`` in a trace):
the grid walks a row's chunks in order, innermost and sequential, for a block
of ``HEAD_BLOCK`` heads; ``C B^T`` [Q, Q] is computed and masked once a chunk
for all heads of the block; each head's decays ``exp(s_t - s_r)`` and its
masked product live in VMEM only, and the block's states [heads, N, P]
float32 are carried from chunk to chunk in scratch (the heads that share a
128-lane tile side by side: ``x`` is read where it lies, never transposed). ``dt A``, its running
sums (taken outside, a [B, T, H] float32 pass), the exponentials, the state
and the ``D x`` skip are float32; ``x``, ``B``, ``C`` and the decayed
products enter the matmuls in ``x``'s dtype and accumulate in float32. No
array of [B, T / Q, H, Q, Q] ever reaches HBM.

Right padding needs no mask: the recurrence is causal, so a padding position
changes nothing before it, and nobody reads what it holds. A length that is
not a whole number of chunks is padded with ``dt = 0``, ``x = 0``: the state
passes through unchanged.

``scan_xla`` is the plain-XLA twin of the same chunked form (a ``lax.scan``
over chunks), what runs where Mosaic does not (``ops/backend.py`` decides, as
for ``paged_attention_ref``). ``chunks_useful`` and ``chunks_visited`` count
the chunks that hold a real token and the chunks walked at the forwarded
shape, as ``block_attention.pairs_allowed`` / ``pairs_visited`` count pairs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.backend import pallas_interpret

SSD_KERNEL_NAME = "ssd_chunk_scan"
CHUNK = 256  # Mamba-2's published chunk
HEAD_BLOCK = 16  # heads a grid step works through with one C B^T
_VMEM_LIMIT = 64 * 1024 * 1024


def chunks_useful(lengths, chunk: int = CHUNK) -> int:
    """Chunks that hold a real token, over rows of ``lengths`` real tokens (one layer)."""
    return int(sum(-(-int(t) // chunk) for t in lengths))


def chunks_visited(rows: int, width: int, chunk: int = CHUNK) -> int:
    """Chunks the scan walks for ``rows`` rows forwarded at ``width`` positions (one layer)."""
    return rows * -(-width // min(chunk, width))


def _running_sums(dt, A, chunk: int):
    """``s`` [B, T, H] float32: the sum of ``dt A`` from each chunk's start to each position."""
    batch, length, heads = dt.shape
    a = dt.astype(jnp.float32) * A.astype(jnp.float32)
    return jnp.cumsum(a.reshape(batch, length // chunk, chunk, heads), axis=2).reshape(dt.shape)


def _padded(x, dt, B, C, chunk: int):
    pad = -x.shape[1] % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (B, C))
    return x, dt, B, C


def scan_xla(x, dt, A, B, C, D, chunk: int = CHUNK):
    """The chunked form in plain XLA, one chunk of every row and head at a
    time; roundings as the kernel's."""
    batch, length, heads, width = x.shape
    chunk = min(chunk, length)
    x, dt, B, C = _padded(x, dt, B, C, chunk)
    s = _running_sums(dt, A, chunk)
    n = x.shape[1] // chunk
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    skip = D.astype(jnp.float32)[None, None, :, None]

    def chunked(a):  # [B, n Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(a.reshape((batch, n, chunk) + a.shape[2:]), 1, 0)

    def step(state, part):  # state [B, H, P, N] float32
        xc, dtc, sc, bc, cc = part
        x32 = xc.astype(jnp.float32)
        xdt = x32 * dtc.astype(jnp.float32)[..., None]
        g = jnp.einsum("btn,brn->btr", cc, bc, preferred_element_type=jnp.float32)
        decay = jnp.exp(jnp.minimum(sc[:, :, None, :] - sc[:, None, :, :], 0.0))  # [B, t, r, H]
        m = jnp.where(causal[None, :, :, None], g[..., None] * decay, 0.0)
        y = jnp.einsum("btrh,brhp->bthp", m.astype(x.dtype), xdt.astype(x.dtype), preferred_element_type=jnp.float32)
        read = jnp.einsum("btn,bhpn->bthp", cc, state.astype(x.dtype), preferred_element_type=jnp.float32)
        y = y + jnp.exp(sc)[..., None] * read + skip * x32
        last = sc[:, -1]  # [B, H]
        weighed = (xdt * jnp.exp(last[:, None] - sc)[..., None]).astype(x.dtype)
        added = jnp.einsum("brhp,brn->bhpn", weighed, bc, preferred_element_type=jnp.float32)
        state = jnp.exp(last)[..., None, None] * state + added
        return state, y.astype(x.dtype)

    first = jnp.zeros((batch, heads, width, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, first, tuple(chunked(a) for a in (x, dt, s, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(batch, n * chunk, heads, width)[:, :length]


def _kernel(x_ref, s_col_ref, dt_col_ref, s_row_ref, c_ref, b_ref, d_ref, y_ref, state_ref, *, heads, pack, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    c, bt = c_ref[0], b_ref[0].T  # [Q, N], [N, Q]: transposed here, once a chunk, so that every matmul is a plain one
    dtype = c.dtype
    g = jnp.dot(c, bt, preferred_element_type=jnp.float32)  # C_t . B_r, for every head of the block
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    g = jnp.where(r <= t, g, 0.0)
    s_col, dt_col, s_row, skip = s_col_ref[0, 0], dt_col_ref[0, 0], s_row_ref[0], d_ref[0]
    lanes = x_ref.shape[2] // (heads // pack)  # of one tile: `pack` heads side by side
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // (lanes // pack)

    def by_head(values):
        """One value a head of the tile (a column [Q, 1] or a scalar), laid over that head's lanes."""
        out = values[0]
        for i in range(1, pack):
            out = jnp.where(head_of_lane == i, values[i], out)
        return out

    for tile in range(heads // pack):
        own = range(tile * pack, (tile + 1) * pack)
        at = slice(tile * lanes, (tile + 1) * lanes)
        x32 = x_ref[0, :, at].astype(jnp.float32)  # [Q, lanes]
        # [Q, lanes] whatever the tile holds (one head's column alone would be [Q, 1])
        s_t = by_head([s_col[:, h : h + 1] for h in own]) + jnp.zeros((1, lanes), jnp.float32)
        xdt = x32 * by_head([dt_col[:, h : h + 1] for h in own])
        y = jnp.zeros((chunk, lanes), jnp.float32)
        for i, h in enumerate(own):
            # above the diagonal s_t - s_r is positive and g is 0: the minimum keeps the exponential finite
            decay = jnp.exp(jnp.minimum(s_col[:, h : h + 1] - s_row[h : h + 1, :], 0.0))  # [Q, Q]
            mixed = jnp.dot((g * decay).astype(dtype), xdt.astype(dtype), preferred_element_type=jnp.float32)
            y = jnp.where(head_of_lane == i, mixed, y)  # the product is head h's on its own lanes only
        state = state_ref[tile]  # [N, lanes]
        y += jnp.exp(s_t) * jnp.dot(c, state.astype(dtype), preferred_element_type=jnp.float32)
        y += by_head([skip[0, h] for h in own]) * x32
        y_ref[0, :, at] = y.astype(y_ref.dtype)
        last = s_t[chunk - 1 :, :]  # [1, lanes]: each head's sum over the whole chunk
        weighed = (xdt * jnp.exp(last - s_t)).astype(dtype)
        state_ref[tile] = jnp.exp(last) * state + jnp.dot(bt, weighed, preferred_element_type=jnp.float32)


def scan_pallas(x, dt, A, B, C, D, chunk: int = CHUNK):
    """The kernel. ``x`` stays as it lies ([B, T, H P]: heads side by side on
    the lanes, ``128 / P`` of them a 128-lane tile, worked through together);
    the running sums arrive both with positions on the sublanes ([.., T,
    heads]: ``s_t`` as a column) and on the lanes ([.., heads, T]: ``s_r`` as
    a row), and ``B`` transposed, so that every matmul inside is a plain one."""
    batch, length, heads, width = x.shape
    states = B.shape[-1]
    chunk = min(chunk, length)
    head_block = min(HEAD_BLOCK, heads)
    pack = min(head_block, max(1, 128 // width))
    if heads % head_block or head_block % pack:
        raise ValueError(f"ssd_scan: {heads} heads are no whole number of blocks of {head_block}, in tiles of {pack}")
    x, dt, B, C = _padded(x, dt, B, C, chunk)
    padded = x.shape[1]
    blocks = heads // head_block
    s = _running_sums(dt, A, chunk)

    def columns(a):  # [B, T, H] -> [B, blocks, T, head_block]
        return a.astype(jnp.float32).reshape(batch, padded, blocks, head_block).transpose(0, 2, 1, 3)

    y = pl.pallas_call(
        functools.partial(_kernel, heads=head_block, pack=pack, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((batch, padded, heads * width), x.dtype),
        grid=(batch, blocks, padded // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, head_block * width), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, 1, chunk, head_block), lambda b, j, i: (b, j, i, 0)),
            pl.BlockSpec((1, 1, chunk, head_block), lambda b, j, i: (b, j, i, 0)),
            pl.BlockSpec((1, head_block, chunk), lambda b, j, i: (b, j, i)),
            pl.BlockSpec((1, chunk, states), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, states), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, head_block), lambda b, j, i: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, head_block * width), lambda b, j, i: (b, i, j)),
        scratch_shapes=[pltpu.VMEM((head_block // pack, states, pack * width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=pallas_interpret(),
        name=SSD_KERNEL_NAME,
    )(
        x.reshape(batch, padded, heads * width), columns(s), columns(dt), s.transpose(0, 2, 1),
        C, B, D.astype(jnp.float32).reshape(blocks, 1, head_block),
    )
    return y.reshape(batch, padded, heads, width)[:, :length]


def scan(x, dt, A, B, C, D, chunk: int = CHUNK):
    """``y`` [B, T, H, P] of the recurrence above: the kernel on a TPU, its
    XLA twin elsewhere."""
    if pallas_interpret():
        return scan_xla(x, dt, A, B, C, D, chunk)
    return scan_pallas(x, dt, A, B, C, D, chunk)
