"""Pallas TPU kernel: ragged paged-attention for batched decode.

The generation plane (pathway_tpu/generate/) keeps every sequence's KV
state in fixed-size pages of a shared block pool, with a per-sequence
page table mapping logical page index -> physical page id (PAPERS.md,
Ragged Paged Attention, https://arxiv.org/pdf/2604.15464).  One decode
step asks, for each sequence b in the batch, attention of ONE query
token against that sequence's first ``seq_lens[b]`` cached tokens — a
ragged read over scattered pages, which is exactly what the
scalar-prefetch grid is for: the page table is prefetched into SMEM and
the KV block index_map reads it, so grid step (b, j) stages sequence
b's j-th logical page (one [H, P, Dp] tile) into VMEM without ever
materializing a gathered [B, L, H, Dp] tensor in HBM.

Layout honors the Mosaic (8, 128) tiling rule (a kernel that passes in
interpret mode has not thereby lowered):

* pools are ``[n_pages, H, P, Dp]`` with ``Dp = head_dim`` padded up to
  a 128-lane multiple (``lane_pad``); the padded tail lanes are zero in
  both q and k so dot products are unchanged, and v's zero tail keeps
  the output padding zero;
* every block's last two dims are (P, Dp) / (H, Dp): each either
  divides (8, 128) or equals the corresponding array dim —
  ``validate_lowering`` asserts this statically via the shared
  ``check_tpu_block_rules`` so tests gate lowering without TPU
  hardware.

Softmax over the ragged length is the standard online (flash) rescale
across grid steps j — running max/denominator live in VMEM scratch, the
unnormalized accumulator in a third scratch, and the output block is
written once at the last page.  Fully-masked slots (padded batch rows,
seq_len 0) use a large-negative finite mask value instead of -inf so
the rescale never produces NaN; their denominator stays 0 and the
final write zero-fills them.

``paged_attention_ref`` is the jitted pure-JAX twin — what the decode
step runs where the kernel does not compile (any backend but a TPU) and
the differential oracle the tests pin the kernel against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# shared 8x128 gate: analysis/lowering.py is the single source of truth
# for the Mosaic tiling rules (re-exported for existing callers)
from pathway_tpu.ops.backend import pallas_interpret
from pathway_tpu.analysis.lowering import (  # noqa: F401
    LoweringRuleViolation,
    RULE_LANE_PAD,
    check_block_specs,
    check_tpu_block_rules,
    lane_pad,
)

# mask value for invalid key positions: large-negative finite (an -inf
# mask makes the online-softmax rescale NaN on fully-masked pages)
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _specs(b: int, h: int, p: int, dp: int, n_pages: int, max_pages: int):
    """(grid, in_specs, out_specs, out_shape) for the decode kernel —
    the single source for the kernel's layout, shared by the caller and
    the static lowering gate so they cannot drift apart.  Index maps
    take the scalar-prefetch refs (page_tables, seq_lens) after the
    grid indices."""
    grid = (b, max_pages)
    in_specs = [
        # q: one sequence's single query token, all heads
        (
            pl.BlockSpec((1, h, dp), lambda i, j, pt, sl: (i, 0, 0)),
            (b, h, dp),
        ),
        # k/v: the physical page the sequence's j-th logical page maps
        # to — the ragged indirection lives entirely in this index_map
        (
            pl.BlockSpec(
                (1, h, p, dp), lambda i, j, pt, sl: (pt[i, j], 0, 0, 0)
            ),
            (n_pages, h, p, dp),
        ),
        (
            pl.BlockSpec(
                (1, h, p, dp), lambda i, j, pt, sl: (pt[i, j], 0, 0, 0)
            ),
            (n_pages, h, p, dp),
        ),
    ]
    out_specs = [
        (
            pl.BlockSpec((1, h, dp), lambda i, j, pt, sl: (i, 0, 0)),
            (b, h, dp),
        )
    ]
    out_shape = jax.ShapeDtypeStruct((b, h, dp), jnp.float32)
    return grid, in_specs, out_specs, out_shape


def validate_lowering(
    b: int, h: int, p: int, dp: int, n_pages: int, max_pages: int
) -> None:
    """Assert every block spec the kernel will use satisfies the Mosaic
    TPU rule — the compiled-mode test gate."""
    if dp % 128 != 0:
        raise LoweringRuleViolation(
            RULE_LANE_PAD,
            f"head_dim pool width {dp} is not lane-padded (multiple of "
            f"128); pad with lane_pad() — got lane_pad={lane_pad(dp)}",
        )
    grid, in_specs, out_specs, _ = _specs(b, h, p, dp, n_pages, max_pages)
    check_block_specs(in_specs + out_specs)


def _decode_kernel(
    p: int,
    sm_scale: float,
    pt_ref,  # scalar-prefetch: [B, max_pages] page table
    sl_ref,  # scalar-prefetch: [B] sequence lengths
    q_ref,  # [1, H, Dp]
    k_ref,  # [1, H, P, Dp]
    v_ref,  # [1, H, P, Dp]
    o_ref,  # [1, H, Dp]
    m_scr,  # [H, 128] running max (all lanes equal)
    l_scr,  # [H, 128] running denominator (all lanes equal)
    acc_scr,  # [H, Dp] unnormalized output accumulator
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    h, dp = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full((h, 128), _NEG, jnp.float32)
        l_scr[:] = jnp.zeros((h, 128), jnp.float32)
        acc_scr[:] = jnp.zeros((h, dp), jnp.float32)

    q = q_ref[0].astype(jnp.float32)  # [H, Dp]
    k = k_ref[0].astype(jnp.float32)  # [H, P, Dp]
    v = v_ref[0].astype(jnp.float32)
    # per-head scores of the query against this page: [H, P].  Unrolled
    # over heads as 2-D dots — Mosaic only lowers 2-D dot_general (a
    # batched [H,Dp]x[H,P,Dp] contraction is interpret-green but fails
    # TPU lowering; the ledger's AOT export proves this shape)
    s_rows = []
    for hh in range(h):
        s_rows.append(
            jax.lax.dot_general(
                q[hh : hh + 1, :],
                k[hh],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    s = jnp.concatenate(s_rows, axis=0) * sm_scale
    # ragged mask: token index j*P + col vs this sequence's length
    pos = j * p + jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    valid = pos < sl_ref[b]  # [1, P]
    s = jnp.where(valid, s, _NEG)

    m_prev = m_scr[:]  # [H, 128]
    l_prev = l_scr[:]
    m_cur = jnp.max(s, axis=1, keepdims=True)  # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, (h, 128)))
    alpha = jnp.exp(m_prev - m_new)  # [H, 128] rescale of the old state
    # exp weights for this page, hard-zeroed on masked lanes (on a
    # fully-masked page m_new stays _NEG and exp(s - m_new) would be 1)
    w = jnp.exp(s - m_new[:, :1]) * valid.astype(jnp.float32)  # [H, P]
    l_new = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(w, axis=1, keepdims=True), (h, 128)
    )
    # weighted page values, same per-head 2-D unroll: [H, Dp]
    pv_rows = []
    for hh in range(h):
        pv_rows.append(
            jax.lax.dot_general(
                w[hh : hh + 1, :],
                v[hh],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    pv = jnp.concatenate(pv_rows, axis=0)
    acc_scr[:] = acc_scr[:] * alpha[:, :1] + pv
    m_scr[:] = m_new
    l_scr[:] = l_new

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[:, :1]  # [H, 1]
        # fully-masked slots (padded batch rows) have l == 0: zero-fill
        o = jnp.where(l > 0.0, acc_scr[:] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = o


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "interpret")
)
def paged_attention(
    q: jax.Array,  # [B, H, Dp] f32 query tokens (padded lanes zero)
    k_pool: jax.Array,  # [n_pages, H, P, Dp]
    v_pool: jax.Array,  # [n_pages, H, P, Dp]
    page_tables: jax.Array,  # [B, max_pages] int32 physical page ids
    seq_lens: jax.Array,  # [B] int32 valid tokens per sequence
    *,
    sm_scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """One ragged paged-attention decode step: [B, H, Dp] outputs.
    ``interpret=None`` takes the mode from the backend (compiled on a
    TPU, see ops/backend.py); tests pass it explicitly."""
    if interpret is None:
        interpret = pallas_interpret()
    b, h, dp = q.shape
    n_pages, _h, p, _dp = k_pool.shape
    max_pages = page_tables.shape[1]
    grid, in_specs, out_specs, out_shape = _specs(
        b, h, p, dp, n_pages, max_pages
    )
    kernel = functools.partial(_decode_kernel, p, float(sm_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[spec for spec, _ in in_specs],
        out_specs=out_specs[0][0],
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, dp), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(
        page_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        q.astype(jnp.float32),
        k_pool,
        v_pool,
    )


@jax.jit
def paged_attention_ref(
    q: jax.Array,  # [B, H, Dp]
    k_pool: jax.Array,  # [n_pages, H, P, Dp]
    v_pool: jax.Array,
    page_tables: jax.Array,  # [B, max_pages]
    seq_lens: jax.Array,  # [B]
    *,
    sm_scale: float | jax.Array = 1.0,
) -> jax.Array:
    """Jitted pure-JAX twin — gathers each sequence's pages dense and
    runs a masked softmax.  The decode step's attention off the TPU and
    the differential oracle for the Pallas kernel."""
    b, h, dp = q.shape
    _n, _h, p, _dp = k_pool.shape
    max_pages = page_tables.shape[1]
    k = k_pool[page_tables]  # [B, max_pages, H, P, Dp]
    v = v_pool[page_tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, h, max_pages * p, dp)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, h, max_pages * p, dp)
    s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32), k) * sm_scale
    pos = jnp.arange(max_pages * p, dtype=jnp.int32)
    mask = pos[None, None, :] < seq_lens[:, None, None]
    s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    w = jnp.exp(s - m) * mask  # hard-zero the masked tail
    l = jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.einsum("bhl,bhld->bhd", w, v) / jnp.maximum(l, 1e-30)
    return jnp.where(l > 0.0, out, 0.0)
