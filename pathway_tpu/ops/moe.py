"""Sparse expert layer: route, dispatch, grouped matmul, combine.

An expert layer sends each token to ``top_k`` of ``n_experts`` gated
feed-forward experts and sums what they return, weighted by the router.
Nothing is dropped and there is no capacity factor: the (token, expert)
pairs are sorted by expert into contiguous groups of rows (``dispatch``),
every group is multiplied by its own expert's weights in one grouped matmul
(``grouped_ffn``), and each token gathers its rows back (``combine``).

The layer is told which experts it holds (``experts_held=(first, count)``,
all of them by default). It always routes over all ``n_experts``; pairs that
go to an expert held elsewhere are left out of the result, so the parts that
disjoint shares compute add up to the whole layer's routed output. Rows of
padding positions (``valid`` false) are routed nowhere.

The grouped matmul is a Pallas kernel over groups padded to ``TILE_ROWS``
rows, so that every row tile belongs to one expert and the expert's weights
are staged in VMEM once a group; its device ops are called
``GMM_KERNEL_NAME`` in a trace. Measured on a v5e against
``jax.lax.ragged_dot`` at 16,384 x 4 rows into 64 groups of 3584 x 1024
(PERF.md section 6, PR 28): 11.1 against 16.6 ms for the three matmuls of a
layer.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.ops.backend import pallas_interpret

GMM_KERNEL_NAME = "moe_grouped_matmul"
TILE_ROWS = 128  # rows of one expert's tile: 256 and 512 run the matmuls no faster and pad more
_VMEM_LIMIT = 64 * 1024 * 1024  # two weight blocks of 3584 x 1024 bf16 and the row tiles


def route(h, router, bias, *, top_k: int, scale: float, normalise: bool = True):
    """Sigmoid router with a bias-corrected choice (``noaux_tc``, one group).

    ``h`` [T, d]; ``router`` [d, E]; ``bias`` [E]. Scores, choice and weights
    are float32: ``s = sigmoid(h W_r)``, the choice is the top-k of
    ``s + bias``, the weights are ``scale * s[choice] / sum(s[choice])``.
    Returns (weights [T, k] float32, choice [T, k] int32)."""
    logits = jnp.dot(
        h.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, choice, axis=1)
    if normalise:
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return picked * scale, choice.astype(jnp.int32)


class Plan(NamedTuple):
    """Where every (token, slot) pair goes in the grouped matmul's rows."""

    src: jax.Array  # [R] token feeding each row; T (one past the last) where the row is padding
    dest: jax.Array  # [T, k] row that holds each pair; R where no held expert computes it
    group_sizes: jax.Array  # [held] rows of each held expert, padded to whole tiles
    counts: jax.Array  # [E] valid tokens the router sent to each expert, held here or not


def plan_rows(pairs: int, held: int) -> int:
    """Rows of the grouped matmul's operand: every pair, and room for each
    group's padding, a whole number of tiles."""
    rows = pairs + held * (TILE_ROWS - 1)
    return -(-rows // TILE_ROWS) * TILE_ROWS


def dispatch(choice, valid, n_experts: int, experts_held=None) -> Plan:
    """Sort the pairs by expert (stable, so a token's rows keep their order)
    into groups padded to ``TILE_ROWS`` rows. ``choice`` [T, k] int32,
    ``valid`` [T] bool. Shapes are static; no pair is dropped or duplicated."""
    tokens, top_k = choice.shape
    first, held = experts_held or (0, n_experts)
    pairs = tokens * top_k
    rows = plan_rows(pairs, held)
    flat = jnp.where(jnp.repeat(valid, top_k), choice.reshape(-1), -1)
    counts_all = (flat[:, None] == jnp.arange(n_experts)[None, :]).sum(0, dtype=jnp.int32)
    local = flat - first
    key = jnp.where((flat >= 0) & (local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = counts_all[first : first + held]
    sizes = -(-counts // TILE_ROWS) * TILE_ROWS
    starts = jnp.cumsum(sizes) - sizes  # of each group, in the padded rows
    offsets = jnp.cumsum(counts) - counts  # of each group, in the sorted pairs

    # each row's pair: its group, its rank in the group, the pair sorted there
    row = jnp.arange(rows, dtype=jnp.int32)
    group = (row[:, None] >= (starts + sizes)[None, :]).sum(1, dtype=jnp.int32)
    inside = jnp.minimum(group, held - 1)
    rank = row - starts[inside]
    live = (group < held) & (rank < counts[inside])
    at = jnp.clip(offsets[inside] + rank, 0, pairs - 1)
    src = jnp.where(live, order[at] // top_k, tokens)

    # each pair's row: the inverse of the sort, then the same arithmetic
    where = jnp.argsort(order).astype(jnp.int32)  # position of each pair among the sorted
    sorted_key = key[order]
    sorted_inside = jnp.minimum(sorted_key, held - 1)
    sorted_dest = jnp.where(
        sorted_key < held,
        starts[sorted_inside] + jnp.arange(pairs, dtype=jnp.int32) - offsets[sorted_inside],
        rows,
    )
    dest = sorted_dest[where].reshape(tokens, top_k)
    return Plan(src, dest, sizes, counts_all)


def gather_rows(h, plan: Plan):
    """The grouped matmul's operand [R, d]: each row its token's activations.
    A padding row holds some token's (the last one's): whatever is computed
    from it is never read, since no ``dest`` points at a padding row."""
    return jnp.take(h, plan.src, axis=0, mode="clip")


def _gmm_kernel(tile_group_ref, used_ref, x_ref, w_ref, out_ref):
    del tile_group_ref  # read by the index maps

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)


def grouped_matmul(x, w, group_sizes):
    """``x`` [R, K] whose groups are padded to ``TILE_ROWS`` rows times ``w``
    [G, K, N]: one grid step a row tile, the tile's expert read from a
    prefetched table. Tiles past the last group are skipped, and mapped onto
    the last used tile so that nothing is moved for them; their output rows
    are never written and never read (no ``dest`` points there)."""
    rows, k = x.shape
    groups, _, n = w.shape
    tiles = rows // TILE_ROWS
    ends = jnp.cumsum(group_sizes)
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * TILE_ROWS
    tile_group = jnp.minimum(
        (tile_start[:, None] >= ends[None, :]).sum(1, dtype=jnp.int32), groups - 1
    )
    used = (ends[-1] // TILE_ROWS).astype(jnp.int32).reshape(1)

    def row_tile(i, tile_group_ref, used_ref):
        return (jnp.maximum(jnp.minimum(i, used_ref[0] - 1), 0), 0)

    def weights(i, tile_group_ref, used_ref):
        return (tile_group_ref[i], 0)

    return pl.pallas_call(
        _gmm_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((TILE_ROWS, k), row_tile),
                pl.BlockSpec((k, n), weights),  # expert g is rows g*k .. of the flattened weights
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, n), row_tile),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=pallas_interpret(),
        name=GMM_KERNEL_NAME,
    )(tile_group, used, x, w.reshape(groups * k, n))


def grouped_ffn(x, w_gate, w_up, w_down, group_sizes):
    """Each group of rows through its own gated silu expert:
    ``(silu(x W_gate) * x W_up) W_down``. ``x`` [R, d]; weights [held, d, f]
    and [held, f, d]; multiplies in the rows' dtype, accumulation float32."""
    gate = grouped_matmul(x, w_gate, group_sizes).astype(jnp.float32)
    up = grouped_matmul(x, w_up, group_sizes).astype(jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return grouped_matmul(hidden, w_down, group_sizes)


def combine(y, plan: Plan, weights):
    """Each token's weighted sum of its rows of ``y`` [R, d], float32
    [T, d]; a pair no held expert computed adds nothing. One gather a slot:
    a [T, k, d] gather would be laid out anew before the sum."""
    rows = y.shape[0]
    total = 0.0
    for slot in range(plan.dest.shape[1]):
        dest = plan.dest[:, slot]
        picked = jnp.take(y, dest, axis=0, mode="clip").astype(jnp.float32)
        total = total + jnp.where((dest < rows)[:, None], weights[:, slot, None] * picked, 0.0)
    return total


def rows_computed(counts) -> int:
    """Rows one grouped matmul multiplied, from the held experts' token
    counts: the tiles in use, their padding included."""
    return int(sum(-(-int(c) // TILE_ROWS) * TILE_ROWS for c in counts))


def expert_layer(
    h, valid, router, bias, w_gate, w_up, w_down, *,
    top_k: int, scale: float, normalise: bool = True, experts_held=None,
):
    """The routed part of an expert layer on ``h`` [T, d]: what the held
    experts add, float32 [T, d], the router's token counts [E], and its
    choice [T, k] (-1 at a padding position: routed nowhere)."""
    n_experts = router.shape[1]
    with jax.named_scope("trunk.moe.route"):
        weights, choice = route(h, router, bias, top_k=top_k, scale=scale, normalise=normalise)
        plan = dispatch(choice, valid, n_experts, experts_held)
    with jax.named_scope("trunk.moe.experts"):
        y = grouped_ffn(gather_rows(h, plan), w_gate, w_up, w_down, plan.group_sizes)
    with jax.named_scope("trunk.moe.combine"):
        routed = combine(y, plan, weights)
    return routed, plan.counts, jnp.where(valid[:, None], choice, -1)
