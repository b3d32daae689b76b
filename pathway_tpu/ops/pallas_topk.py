"""Pallas TPU kernel: fused KNN scoring + block-local top-k.

The hot op of the retrieval path (reference: the brute-force KNN inner
loop, src/external_integration/brute_force_knn_integration.rs:22, here
mapped onto the MXU): for each grid step one [BLK, D] corpus tile is
staged in VMEM, scored against the [B, D] queries on the MXU, masked, and
reduced to the tile's top-k (k max/argmax/suppress passes on the VPU) —
so only [B, nblk*KP] candidates ever return to HBM instead of the full
[B, N] score matrix. A final lax.top_k merges block winners (exact: every
global top-k element is within the top-k of its own block, the merge that
the 1,024-block path of ops/knn._masked_topk shares; its selecting first
stage, _blockmax_topk, is another method). Runs in interpreter mode
off-TPU so tests cover it on the CPU backend.

TPU lowering constraint: the last two dims of every
block must be divisible by (8, 128) or equal the overall array dims. The
outputs are therefore laid out 2-D as [B, nblk*KP] where KP = k padded up
to a multiple of 128 — each grid step writes its own lane-aligned (B, KP)
tile (KP % 128 == 0; B equals the array dim), with the real k winners in
the leading lanes and -inf/0 padding after. The caller reshapes to
[B, nblk, KP] and slices [..., :k]. `check_tpu_block_rules` asserts the
constraint statically so tests gate it without TPU hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the 8x128 rules live in ONE place (analysis/lowering.py) — re-exported
# here for the existing test gates and callers
from pathway_tpu.ops.backend import pallas_interpret
from pathway_tpu.analysis.lowering import (  # noqa: F401
    check_block_specs,
    check_tpu_block_rules,
    lane_pad,
)

BLK = 1024


def _kpad(k: int) -> int:
    """k padded up to the TPU lane width (multiple of 128)."""
    return lane_pad(k)


def _specs(bq: int, d: int, n: int, k: int):
    """(grid, in_specs, out_specs, out_shapes, nblk, kp) for the block-
    top-k call — the single source for the kernel's layout, shared by the
    caller and the static test gate so they can't drift apart."""
    nblk = n // BLK
    kp = _kpad(k)
    in_specs = [
        (pl.BlockSpec((bq, d), lambda i: (0, 0)), (bq, d)),
        (pl.BlockSpec((BLK, d), lambda i: (i, 0)), (n, d)),
        (pl.BlockSpec((1, BLK), lambda i: (0, i)), (1, n)),
    ]
    out_specs = [
        (pl.BlockSpec((bq, kp), lambda i: (0, i)), (bq, nblk * kp)),
        (pl.BlockSpec((bq, kp), lambda i: (0, i)), (bq, nblk * kp)),
    ]
    out_shapes = [
        jax.ShapeDtypeStruct((bq, nblk * kp), jnp.float32),
        jax.ShapeDtypeStruct((bq, nblk * kp), jnp.int32),
    ]
    return (nblk,), in_specs, out_specs, out_shapes, nblk, kp


def _topk_block_kernel(k: int, kp: int, q_ref, c_ref, valid_ref, sc_ref, ix_ref):
    # q: [B, D] f32/bf16; c: [BLK, D]; valid: [1, BLK] f32 (1.0/0.0)
    q = q_ref[:]
    c = c_ref[:]
    s = jax.lax.dot_general(
        q,
        c,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, BLK]
    s = jnp.where(valid_ref[:] > 0.5, s, -jnp.inf)
    b = s.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # float copy for the argmax reduction: Mosaic has no integer
    # reduce_min lowering, and BLK (< 2^24) is exact in f32
    colsf = cols.astype(jnp.float32)
    out_cols = jax.lax.broadcasted_iota(jnp.int32, (b, kp), 1)

    def body(i, carry):
        s_cur, _sc, _ix = carry
        m = jnp.max(s_cur, axis=1)  # [B]
        is_max = s_cur == m[:, None]
        # first column attaining the max
        a = jnp.min(
            jnp.where(is_max, colsf, float(BLK)), axis=1
        ).astype(jnp.int32)
        # one-hot lane write (dynamic per-lane .at[] scatters lower poorly
        # on the VPU; a masked select vectorizes)
        hit = out_cols == i
        sc = jnp.where(hit, m[:, None], _sc)
        ix = jnp.where(hit, a[:, None], _ix)
        suppress = cols == a[:, None]
        s_next = jnp.where(suppress, -jnp.inf, s_cur)
        return s_next, sc, ix

    sc0 = jnp.full((b, kp), -jnp.inf, jnp.float32)
    ix0 = jnp.zeros((b, kp), jnp.int32)
    _s, sc, ix = jax.lax.fori_loop(0, k, body, (s, sc0, ix0))
    sc_ref[:] = sc
    ix_ref[:] = ix


@functools.partial(
    jax.jit, static_argnames=("k", "interpret")
)
def pallas_block_topk(
    queries: jax.Array,  # [B, D]
    prep: jax.Array,  # [N, D] prepared corpus (N multiple of BLK)
    valid: jax.Array,  # [N] bool
    k: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-block candidates: ([B, nblk, k] scores, [B, nblk, k] global
    indices).  ``interpret=None`` takes the mode from the backend
    (compiled on a TPU, see ops/backend.py)."""
    if interpret is None:
        interpret = pallas_interpret()
    bq, d = queries.shape
    n = prep.shape[0]
    assert n % BLK == 0, "pad the corpus to a multiple of BLK"
    validf = valid.astype(jnp.float32).reshape(1, n)
    grid, in_specs, out_specs, out_shapes, nblk, kp = _specs(bq, d, n, k)
    kernel = functools.partial(_topk_block_kernel, k, kp)
    sc, ix = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec for spec, _ in in_specs],
        out_specs=[spec for spec, _ in out_specs],
        out_shape=out_shapes,
        interpret=interpret,
    )(queries, prep, validf)
    sc = sc.reshape(bq, nblk, kp)[:, :, :k]
    ix = ix.reshape(bq, nblk, kp)[:, :, :k]
    # local -> global indices
    ix = ix + (jnp.arange(nblk, dtype=jnp.int32) * BLK)[None, :, None]
    return sc, ix


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
def pallas_dense_topk(
    queries: jax.Array,  # [B, D] raw f32 queries
    prep: jax.Array,  # [N, D] prepared corpus (normalized/cast)
    valid: jax.Array,
    k: int,
    metric: str = "dot",  # dot | cosine
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Exact dense top-k via the Pallas block kernel + lax.top_k merge.
    Owns the query-side metric handling (normalize + cast to the corpus
    dtype) so every caller scores identically to dense_topk_prepared."""
    if metric == "cosine":
        queries = queries / (
            jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-30
        )
    queries = queries.astype(prep.dtype)
    sc, ix = pallas_block_topk(queries, prep, valid, k, interpret=interpret)
    b = sc.shape[0]
    sc_f = sc.reshape(b, -1)
    ix_f = ix.reshape(b, -1)
    scores, pos = jax.lax.top_k(sc_f, k)
    idx = jnp.take_along_axis(ix_f, pos, axis=1)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx


def supported(n: int, k: int) -> bool:
    return n % BLK == 0 and k <= BLK


def validate_lowering(bq: int, d: int, n: int, k: int) -> None:
    """Assert every block spec the kernel will use satisfies the TPU
    lowering rule. Used by the compiled-mode test gate."""
    _grid, in_specs, out_specs, _shapes, _nblk, _kp = _specs(bq, d, n, k)
    check_block_specs(in_specs + out_specs)
