"""The hyper-connection residual's two passes over its streams.

A sub-layer of a trunk whose residual is ``n`` streams (manifold-constrained
hyper-connections, arXiv:2512.24880) reads ``u = H_pre X`` and leaves ``X <-
H_res X + H_post^T F(u)``; the coefficients are made, token by token, from
the streams themselves. The streams are the largest arrays of such a forward
([n, B, T, d]), so a sub-layer's cost is how often it passes over them. Two
Pallas kernels over blocks of whole rows (``block`` positions of one batch
row, all ``d`` columns) pass over them once each:

* ``mix_in`` reads a block [n, block, d] once and from it makes the sum of
  squares over all streams, the ``n + n + n * n`` raw projections (the
  streams' dtype on the MXU, float32 accumulation), the RMS scale, ``H_pre =
  sigmoid(.)``, ``H_post = 2 sigmoid(.)``, ``H_res = Sinkhorn(exp(clip(.)))``
  (float32, ``eps`` in every denominator, rows then columns) and ``u = sum_i
  H_pre[i] X[i]`` (float32 products and sum, one cast). It writes ``u`` and
  the coefficients, packed (``coefficients`` unpacks them).
* ``mix_out`` reads the same block, the sub-layer's output and the packed
  coefficients once and writes the new streams in the old ones' place
  (``input_output_aliases``; a block reads only the rows it writes): ``X'[i] =
  sum_j H_res[i, j] X[j] + H_post[i] F(u)``, float32 products and sums, one
  cast.

The coefficient arithmetic has the positions in the lanes ([coefficient,
block]); the two places where a coefficient multiplies a row of width ``d``
take them transposed. Inside a block the elementwise passes go ``_CHUNK``
rows at a time, so the code of a kernel is a few hundred vector operations
whatever the block. The arrays keep the caller's [B, T] axes: a reshape
between a kernel and the norm that reads its result makes XLA write that
result out again in float32 (PERF.md section 6, PR 37). The device ops are
called ``MIX_IN_KERNEL_NAME`` and ``MIX_OUT_KERNEL_NAME`` in a trace;
``ops/backend.py`` decides interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.backend import pallas_interpret

MIX_IN_KERNEL_NAME = "mhc_mix_in"
MIX_OUT_KERNEL_NAME = "mhc_mix_out"
_BLOCKS = (256, 128, 64, 32, 16)  # positions of a block, the largest that divides T (128, 256 and 512 run alike); 16: a bf16 tile's sublanes
_CHUNK = 16  # rows of one step of a pass inside a block: a bf16 tile's sublanes
_LANES = (512, 256, 128)  # columns of one step, the widest that divides d: five operands of a chunk fit the registers
_VMEM_LIMIT = 64 * 1024 * 1024  # a streams block in and out, twice each, with room to spare


def row_block(length: int) -> int:
    """Positions of one block for rows of ``length`` positions (a multiple of
    the smallest block)."""
    return next(block for block in _BLOCKS if length % block == 0)


def packed_rows(n: int) -> int:
    """Rows of the packed coefficients: H_pre, H_post, then H_res row by row."""
    return (2 + n) * n


def _pad_positions(x, axis: int):
    """``x`` with its position axis padded with zeros to a whole number of the smallest block."""
    length = x.shape[axis]
    padded = -(-length // _BLOCKS[-1]) * _BLOCKS[-1]
    if padded == length:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, padded - length)
    return jnp.pad(x, widths)


def _sinkhorn(res, iters: int, eps: float):
    """``res``: the matrix's rows, each [n, block] (its columns in the
    sublanes, positions in the lanes). A round normalises the rows, then the
    columns."""

    def one_round(_, res):
        res = tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in res)
        total = functools.reduce(jnp.add, res)
        return tuple(r / (total + eps) for r in res)

    return jax.lax.fori_loop(0, iters, one_round, tuple(res))


def _by_chunks(block: int, body) -> None:
    """``body(rows)`` for every ``_CHUNK`` rows of a block, one after another."""

    def step(c, carry):
        body(pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK))
        return carry

    jax.lax.fori_loop(0, block // _CHUNK, step, 0)


def _lane_slices(d: int) -> list[slice]:
    """``d`` columns in slices a chunk of which fits the vector registers."""
    width = next((w for w in _LANES if d % w == 0), d)
    return [slice(at, at + width) for at in range(0, d, width)]


def _mix_in_kernel(
    streams_ref, proj_ref, alpha_ref, bias_ref, mixed_ref, coef_ref, squares_ref, pre_ref, *,
    n: int, iters: int, eps: float, rms_eps: float, clamp: tuple[float, float],
):
    block, d = mixed_ref.shape
    slices = _lane_slices(d)
    raw = functools.reduce(
        jnp.add, [jnp.dot(streams_ref[i], proj_ref[i], preferred_element_type=jnp.float32) for i in range(n)]
    )  # [block, packed]

    def squares_of(rows):
        squares = [
            jnp.square(streams_ref[i, rows, at].astype(jnp.float32)) for i in range(n) for at in slices
        ]
        squares_ref[rows, :] = jnp.sum(functools.reduce(jnp.add, squares), axis=-1, keepdims=True)

    _by_chunks(block, squares_of)
    scale = jax.lax.rsqrt(squares_ref[...] / (n * d) + rms_eps)  # [block, 1]
    logits = alpha_ref[...] * (raw * scale).T + bias_ref[...]  # [packed, block]: positions in the lanes
    pre = jax.nn.sigmoid(logits[:n])
    coef_ref[:n] = pre
    coef_ref[n : 2 * n] = 2.0 * jax.nn.sigmoid(logits[n : 2 * n])
    res = _sinkhorn(
        [jnp.exp(jnp.clip(logits[(2 + i) * n : (3 + i) * n], *clamp)) for i in range(n)], iters, eps
    )
    for i in range(n):
        coef_ref[(2 + i) * n : (3 + i) * n] = res[i]
    pre_ref[...] = pre.T  # [block, n]: a position's coefficients beside its row

    def mix(rows):
        h_pre = pre_ref[rows, :]
        for at in slices:
            terms = [h_pre[:, i : i + 1] * streams_ref[i, rows, at].astype(jnp.float32) for i in range(n)]
            mixed_ref[rows, at] = functools.reduce(jnp.add, terms).astype(mixed_ref.dtype)

    _by_chunks(block, mix)


def mix_in(
    streams, proj, alpha, bias, *,
    iters: int, eps: float, rms_eps: float, clamp: tuple[float, float],
):
    """``streams`` [n, B, T, d]; ``proj`` [n, d, n + n + n * n] in the streams'
    dtype (the norm's gain folded in); ``alpha`` [3] and ``bias`` [n + n + n *
    n] float32, the scalars a_pre, a_post, a_res and the biases of the three
    groups. Returns ``u`` [B, T, d] in the streams' dtype and the packed
    coefficients for ``mix_out`` and ``coefficients``."""
    n, batch, length, d = streams.shape
    packed = packed_rows(n)
    alphas = jnp.repeat(alpha.astype(jnp.float32), jnp.asarray([n, n, n * n]), total_repeat_length=packed)
    padded = _pad_positions(streams, 2)
    block = row_block(padded.shape[2])
    steps = padded.shape[2] // block
    mixed, coef = pl.pallas_call(
        functools.partial(_mix_in_kernel, n=n, iters=iters, eps=eps, rms_eps=rms_eps, clamp=clamp),
        out_shape=(
            jax.ShapeDtypeStruct(padded.shape[1:], streams.dtype),
            jax.ShapeDtypeStruct((batch, steps, packed, block), jnp.float32),
        ),
        grid=(batch, steps),
        in_specs=[
            pl.BlockSpec((n, None, block, d), lambda b, t: (0, b, t, 0)),
            pl.BlockSpec((n, d, packed), lambda b, t: (0, 0, 0)),
            pl.BlockSpec((packed, 1), lambda b, t: (0, 0)),
            pl.BlockSpec((packed, 1), lambda b, t: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, block, d), lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, None, packed, block), lambda b, t: (b, t, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32), pltpu.VMEM((block, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=pallas_interpret(),
        name=MIX_IN_KERNEL_NAME,
    )(padded, proj, alphas[:, None], bias.astype(jnp.float32)[:, None])
    return (mixed if padded is streams else mixed[:, :length]), coef


def _mix_out_kernel(streams_ref, out_ref, coef_ref, new_ref, coef_t_ref, *, n: int):
    block, d = out_ref.shape
    coef_t_ref[...] = coef_ref[...].T  # [block, packed]: a position's coefficients beside its row

    def mix(rows):
        h = coef_t_ref[rows, :]
        for at in _lane_slices(d):
            old = [streams_ref[j, rows, at].astype(jnp.float32) for j in range(n)]
            out32 = out_ref[rows, at].astype(jnp.float32)
            for i in range(n):
                kept = [h[:, (2 + i) * n + j : (2 + i) * n + j + 1] * old[j] for j in range(n)]
                total = functools.reduce(jnp.add, kept) + h[:, n + i : n + i + 1] * out32
                new_ref[i, rows, at] = total.astype(new_ref.dtype)

    _by_chunks(block, mix)


def mix_out(streams, out, coef):
    """``streams`` [n, B, T, d], the sub-layer's output ``out`` [B, T, d] and
    ``mix_in``'s packed coefficients of these streams: the new streams [n, B,
    T, d], written where the old ones were when the caller gives them up."""
    n, batch, length, d = streams.shape
    padded = _pad_positions(streams, 2)
    _, steps, packed, block = coef.shape
    new = pl.pallas_call(
        functools.partial(_mix_out_kernel, n=n),
        out_shape=jax.ShapeDtypeStruct(padded.shape, streams.dtype),
        grid=(batch, steps),
        in_specs=[
            pl.BlockSpec((n, None, block, d), lambda b, t: (0, b, t, 0)),
            pl.BlockSpec((None, block, d), lambda b, t: (b, t, 0)),
            pl.BlockSpec((None, None, packed, block), lambda b, t: (b, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, None, block, d), lambda b, t: (0, b, t, 0)),
        scratch_shapes=[pltpu.VMEM((block, packed), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=pallas_interpret(),
        name=MIX_OUT_KERNEL_NAME,
    )(padded, _pad_positions(out, 1), coef)
    return new if padded is streams else new[:, :, :length]


def coefficients(coef, n: int, length: int):
    """``mix_in``'s packed coefficients as H_pre [n, B, T], H_post [n, B, T]
    and H_res [n, n, B, T], float32."""
    batch, steps, _packed, block = coef.shape
    groups = jnp.transpose(coef, (2, 0, 1, 3)).reshape(2 + n, n, batch, steps * block)[..., :length]
    return groups[0], groups[1], groups[2:]
