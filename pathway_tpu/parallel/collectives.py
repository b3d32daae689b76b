"""Collective patterns over the mesh — the ICI-native replacements for the
reference's timely channel pacts (reference: §2.2 of SURVEY —
timely `Exchange` pact → all_to_all; `Broadcast` → all_gather;
progress frontier exchange → psum; vendored
external/timely-dataflow/communication replaced by XLA collectives)."""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def sharded_rows(mesh: Any, axis: str = "data") -> NamedSharding:
    """Sharding for [N, ...] row-major tables: rows split over `axis`."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Any) -> NamedSharding:
    return NamedSharding(mesh, P())


def exchange_by_shard(values, dest_shard, mesh, axis: str = "data"):
    """Route rows to the mesh shard given per-row in `dest_shard`
    (the Exchange pact: key.shard() % n_workers, reference
    src/engine/dataflow/operators.rs:128) through a real ragged
    `lax.all_to_all` (parallel/exchange.py) — per-device memory is
    O(n_shards × bucket), not O(total rows) like the round-1
    all-gather+mask placeholder.

    Returns (per_shard_values, per_shard_counts): a [n_shards, cap, d]
    array whose block s holds the rows shard s received, and the valid row
    count per block."""
    import numpy as np

    from pathway_tpu.parallel.exchange import ragged_all_to_all

    n_shards = mesh.shape[axis]
    vals = np.ascontiguousarray(values)
    if vals.dtype.itemsize % 4:
        raise TypeError(
            f"exchange_by_shard needs a 4/8-byte element dtype, got "
            f"{vals.dtype}"
        )
    d = vals.shape[1]
    # rows travel as exact int32 bit patterns — no value cast for any dtype
    words = vals.view(np.int32).reshape(vals.shape[0], -1)
    blocks = ragged_all_to_all(
        words, np.asarray(dest_shard, dtype=np.int32), mesh, axis
    )
    cap = max((len(b) for b in blocks), default=0)
    out = np.zeros((n_shards, cap, d), dtype=vals.dtype)
    counts = np.zeros(n_shards, dtype=np.int64)
    for s, b in enumerate(blocks):
        counts[s] = len(b)
        if len(b):
            out[s, : len(b)] = b.view(vals.dtype).reshape(len(b), d)
    return out, counts


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def frontier_allreduce(local_time, mesh, axis: str = "data"):
    """Global frontier = min over shards' local clocks — the tiny all-reduce
    per tick replacing timely's progress-update broadcast
    (reference: timely progress tracking, SURVEY §5.8)."""
    def local(t):
        return jax.lax.pmin(t, axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        check_vma=False,
    )(local_time)
