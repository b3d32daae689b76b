"""Sparse expert layer: route, dispatch, grouped matmul, combine.

An expert layer sends each token to ``top_k`` of ``n_experts`` gated
feed-forward experts and sums what they return, weighted by the router
(``route``: the sigmoid router, bias-corrected where the model has a bias,
or the top-k of the logits softmaxed over the chosen k). The shared experts,
summed, averaged or behind a sigmoid gate, are the trunk's (``_trunk._moe``).
Nothing is dropped and there is no capacity factor: the (token, expert)
pairs are sorted by expert into contiguous groups of rows (``dispatch``),
every group is multiplied by its own expert's weights in one grouped matmul
(``grouped_ffn``), and each token gathers its rows back (``combine``).

The layer is told which experts it holds (``experts_held=(first, count)``,
all of them by default). It always routes over all ``n_experts``; pairs that
go to an expert held elsewhere are left out of the result, so the parts that
disjoint shares compute add up to the whole layer's routed output. Rows of
padding positions (``valid`` false) are routed nowhere.

A chip that holds ``count`` of ``n_experts`` is sent about that share of the
pairs, while the shapes have to hold every pair: the rows are therefore
worked through in passes of ``pass_rows`` rows (twice the share, or all of
them where the chip holds every expert: one pass, no loop), as many as the
rows in use need, so that memory and time follow the rows in use and still
no pair is dropped.

The grouped matmul is a Pallas kernel over groups padded to ``TILE_ROWS``
rows, so that every row tile belongs to one expert. The expert's matrix is
staged in VMEM in column blocks of at most ``_WEIGHT_BLOCK_BYTES``
(``column_block``): the grid walks the column blocks outermost and the row
tiles inside, so a block is staged once a group and the row tiles are read
once a column block. Groups are as many as the chip holds: 256 held of 512
(2048 x 512 matrices, about 200 rows an expert in a 16,384-position forward)
take the same path, one pass of ``pass_rows``. A 3584 x 1024 or 1024 x 3584 matrix is one block, as
before the tiling; a 4096 x 4096 one is four of 4096 x 1024. Its device ops
are called ``GMM_KERNEL_NAME`` in a trace. Measured on a v5e against
``jax.lax.ragged_dot`` at 16,384 x 4 rows into 64 groups of 3584 x 1024
(PERF.md section 6, PR 28): 11.1 against 16.6 ms for the three matmuls of a
layer, 46.5% of their roofline in the cell that runs them; at 16 groups of
4096 x 4096 in four column blocks, 256 to 1,000 rows a group, 29-31% of it
(PERF.md section 6, PR 32).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pathway_tpu.observability.device_scopes import scope
from pathway_tpu.ops.backend import pallas_interpret

GMM_KERNEL_NAME = "moe_grouped_matmul"
TILE_ROWS = 128  # rows of one expert's tile: 256 and 512 run the matmuls no faster and pad more
_VMEM_LIMIT = 64 * 1024 * 1024  # two weight blocks and the row tiles
_WEIGHT_BLOCK_BYTES = 8 * 1024 * 1024  # of one staged block of an expert's matrix: 4096 x 1024 bf16


def route(h, router, bias, *, top_k: int, scale: float, normalise: bool = True, scoring: str = "sigmoid"):
    """``scoring="sigmoid"``: the sigmoid router, its choice corrected by a
    bias where one is given (``noaux_tc``, one group; ``bias=None``: the
    top-k of the scores). ``scoring="softmax"``: the top-k of the logits,
    their weights the softmax over those k (no bias, always normalised).

    ``h`` [T, d]; ``router`` [d, E]; ``bias`` [E]. Scores, choice and weights
    are float32: ``s = sigmoid(h W_r)``, the choice is the top-k of
    ``s + bias``, the weights are ``scale * s[choice] / sum(s[choice])``.
    Returns (weights [T, k] float32, choice [T, k] int32)."""
    logits = jnp.dot(
        h.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        picked, choice = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(picked, axis=1) * scale, choice.astype(jnp.int32)
    scores = jax.nn.sigmoid(logits)
    corrected = scores if bias is None else scores + bias.astype(jnp.float32)
    _, choice = jax.lax.top_k(corrected, top_k)
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
    ``valid`` [T] bool. Shapes are static; no pair is dropped or duplicated.

    Nothing here grows with pairs x experts, and nothing is gathered through
    a group's index: on a v5e the cost of such a gather grows with the
    table (2.3 ms for 196,352 indices into 256 groups, 0.6 into 36). So: one
    sort by expert id; the experts' bounds by binary search in the sorted
    ids; a sorted pair's row is its position plus its group's shift, a
    running sum of marks at each group's first pair; the rows' tokens by one
    scatter, the pairs' rows by a second sort. At the trunks' 16,384
    positions this takes 1.4-2.3 ms on a v5e (PERF.md section 6)."""
    tokens, top_k = choice.shape
    first, held = experts_held or (0, n_experts)
    pairs = tokens * top_k
    rows = plan_rows(pairs, held)
    position = jnp.arange(pairs, dtype=jnp.int32)
    flat = jnp.where(jnp.repeat(valid, top_k), choice.reshape(-1), n_experts)  # padding sorts last
    expert, order = jax.lax.sort((flat, position), num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(expert, jnp.arange(n_experts + 1, dtype=jnp.int32)).astype(jnp.int32)
    counts_all = jnp.diff(bounds)
    counts = counts_all[first : first + held]
    offsets = bounds[first : first + held]  # of each group, in the sorted pairs
    sizes = -(-counts // TILE_ROWS) * TILE_ROWS
    starts = jnp.cumsum(sizes) - sizes  # of each group, in the padded rows

    # each sorted pair's row; a pair for an expert held elsewhere has none
    shift = starts - offsets
    marks = jnp.zeros(pairs, jnp.int32).at[offsets].add(jnp.diff(shift, prepend=0), mode="drop")
    mine = (position >= bounds[first]) & (position < bounds[first + held])
    sorted_dest = jnp.where(mine, position + jnp.cumsum(marks), rows)
    # each row's token (every other pair to an index of its own past the rows, dropped)
    to = jnp.where(mine, sorted_dest, rows + position)
    src = jnp.full(rows, tokens, jnp.int32).at[to].set(order // top_k, mode="drop", unique_indices=True)
    # each pair's row: the sorted pairs' rows put back in pair order
    _, dest = jax.lax.sort((order, sorted_dest), num_keys=1, is_stable=False)
    return Plan(src, dest.reshape(tokens, top_k), sizes, counts_all)


def gather_rows(h, src):
    """The grouped matmul's operand [R, d]: each row its token's activations
    (``src``: a plan's, or a pass's part of it). A padding row holds some
    token's (the last one's): whatever is computed from it is never read,
    since no ``dest`` points at a padding row."""
    return jnp.take(h, src, axis=0, mode="clip")


def _gmm_kernel(tile_group_ref, used_ref, x_ref, w_ref, out_ref):
    del tile_group_ref  # read by the index maps

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        out_ref[...] = jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)


def column_block(k: int, n: int, itemsize: int) -> int:
    """Columns of an expert's [k, n] matrix staged at once: all of them where
    they fit ``_WEIGHT_BLOCK_BYTES``, else the widest whole number of
    128-lane tiles that divides ``n`` and fits."""
    if k * n * itemsize <= _WEIGHT_BLOCK_BYTES or n % 128:
        return n
    fits = [c for c in range(128, n, 128) if n % c == 0 and k * c * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits, default=128)


def tile_groups(group_sizes, tiles: int):
    """The expert each row tile belongs to [tiles], and how many tiles are
    in use [1]."""
    ends = jnp.cumsum(group_sizes)
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * TILE_ROWS
    tile_group = jnp.minimum(
        (tile_start[:, None] >= ends[None, :]).sum(1, dtype=jnp.int32), group_sizes.shape[0] - 1
    )
    return tile_group, (ends[-1] // TILE_ROWS).astype(jnp.int32).reshape(1)


def grouped_matmul(x, w, tile_group, used):
    """``x`` [R, K] whose groups are padded to ``TILE_ROWS`` rows times ``w``
    [G, K, N]: one grid step a column block of the expert's matrix and a row
    tile, the tile's expert read from a prefetched table (``tile_groups``).
    Tiles past the last one in use are skipped, and mapped onto the last
    used tile so that nothing is moved for them; their output rows are never
    written and never read (no ``dest`` points there)."""
    rows, k = x.shape
    groups, _, n = w.shape
    tiles = rows // TILE_ROWS
    block = column_block(k, n, w.dtype.itemsize)

    def row_tile(j, i, tile_group_ref, used_ref):
        return (jnp.maximum(jnp.minimum(i, used_ref[0] - 1), 0), 0)

    def weights(j, i, tile_group_ref, used_ref):
        return (tile_group_ref[i], j)

    def out_tile(j, i, tile_group_ref, used_ref):
        return (jnp.maximum(jnp.minimum(i, used_ref[0] - 1), 0), j)

    return pl.pallas_call(
        _gmm_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // block, tiles),
            in_specs=[
                pl.BlockSpec((TILE_ROWS, k), row_tile),
                pl.BlockSpec((k, block), weights),  # expert g is rows g*k .. of the flattened weights
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, block), out_tile),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=pallas_interpret(),
        name=GMM_KERNEL_NAME,
    )(tile_group, used, x, w.reshape(groups * k, n))


def grouped_ffn(x, w_gate, w_up, w_down, tile_group, used):
    """Each group of rows through its own gated silu expert:
    ``(silu(x W_gate) * x W_up) W_down``. ``x`` [R, d]; weights [held, d, f]
    and [held, f, d]; multiplies in the rows' dtype, accumulation float32."""
    gate = grouped_matmul(x, w_gate, tile_group, used).astype(jnp.float32)
    up = grouped_matmul(x, w_up, tile_group, used).astype(jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return grouped_matmul(hidden, w_down, tile_group, used)


def combine(y, plan: Plan, weights, first_row=0):
    """Each token's weighted sum of its rows of ``y``, float32 [T, d]; ``y``
    [R, d] holds the plan's rows from ``first_row`` on, and a pair whose row
    is not among them (or that no held expert computed) adds nothing. One
    gather a slot: a [T, k, d] gather would be laid out anew before the sum."""
    rows = y.shape[0]
    total = 0.0
    for slot in range(plan.dest.shape[1]):
        dest = plan.dest[:, slot] - first_row
        picked = jnp.take(y, dest, axis=0, mode="clip").astype(jnp.float32)
        here = (dest >= 0) & (dest < rows)
        total = total + jnp.where(here[:, None], weights[:, slot, None] * picked, 0.0)
    return total


def pass_rows(pairs: int, n_experts: int, held: int) -> int:
    """Rows the expert layer works through at once: all of the plan's where
    the chip holds every expert, else room for twice its share of the pairs."""
    share = pairs if held >= n_experts else min(pairs, -(-2 * pairs * held // n_experts))
    return plan_rows(share, held)


def rows_computed(counts) -> int:
    """Rows one grouped matmul multiplied, from the held experts' token
    counts: the tiles in use, their padding included."""
    return int(sum(-(-int(c) // TILE_ROWS) * TILE_ROWS for c in counts))


def expert_layer(
    h, valid, router, bias, w_gate, w_up, w_down, *,
    top_k: int, scale: float, normalise: bool = True, experts_held=None, scoring: str = "sigmoid",
):
    """The routed part of an expert layer on ``h`` [T, d]: what the held
    experts add, float32 [T, d], the router's token counts [E], and its
    choice [T, k] (-1 at a padding position: routed nowhere). ``bias`` is
    the router's correction bias [E], or None where it has none."""
    n_experts = router.shape[1]
    with scope("trunk.moe.route"):
        # the sigmoid router is called as it always was: a stand-in for it need not know the other
        other = {} if scoring == "sigmoid" else {"scoring": scoring}
        weights, choice = route(h, router, bias, top_k=top_k, scale=scale, normalise=normalise, **other)
    with scope("trunk.moe.dispatch"):
        plan = dispatch(choice, valid, n_experts, experts_held)
    rows = plan.src.shape[0]
    tile_group, used = tile_groups(plan.group_sizes, rows // TILE_ROWS)
    step = pass_rows(choice.size, n_experts, w_gate.shape[0])

    def one_pass(first_row, first_tile, src, tiles):
        with scope("trunk.moe.gather"):
            rows_in = gather_rows(h, src)
        with scope("trunk.moe.experts"):
            live = jnp.maximum(used - first_tile, 0)
            y = grouped_ffn(rows_in, w_gate, w_up, w_down, tiles, live)
        with scope("trunk.moe.combine"):
            return combine(y, plan, weights, first_row)

    if step >= rows:  # every row at once
        routed = one_pass(0, 0, plan.src, tile_group)
    else:
        # the rows in use are the first `used` tiles: pass after pass of
        # `step` rows until they are through, mostly one
        tiles_a_pass = step // TILE_ROWS
        passes = -(-rows // step)
        src = jnp.pad(plan.src, (0, passes * step - rows), constant_values=h.shape[0])
        tile_group = jnp.pad(tile_group, (0, passes * tiles_a_pass - tile_group.shape[0]), mode="edge")

        def body(state):
            number, total = state
            part = one_pass(
                number * step, number * tiles_a_pass,
                jax.lax.dynamic_slice(src, (number * step,), (step,)),
                jax.lax.dynamic_slice(tile_group, (number * tiles_a_pass,), (tiles_a_pass,)),
            )
            return number + 1, total + part

        _, routed = jax.lax.while_loop(
            lambda state: state[0] * tiles_a_pass < used[0],
            body,
            (jnp.int32(0), jnp.zeros(h.shape, jnp.float32)),
        )
    return routed, plan.counts, jnp.where(valid[:, None], choice, -1)
