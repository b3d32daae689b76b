"""Ragged all-to-all row exchange over the mesh — the engine's Exchange pact.

The reference routes every record to `key.shard() % n_workers` through timely
exchange channels (reference: src/engine/dataflow/operators.rs:128,432 and
the TCP comm backend, SURVEY §5.8). The TPU-native equivalent is a true
`lax.all_to_all` over ICI: each shard scatters its rows into per-destination
send buckets and one collective rotates the buckets so every shard ends up
holding exactly the rows destined to it.

Unlike an all-gather+mask (round-1 placeholder), per-device memory and ICI
traffic are O(n_shards × bucket_capacity) — proportional to what the shard
actually receives, not to the global table.

Rows are arbitrary typed columns; they travel as exact int32 bit-patterns
(`pack_columns`/`unpack_columns`), so f64/i64/u64 survive bit-for-bit.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import numpy as np

_WORDS = {  # np dtype kind/itemsize -> number of int32 words
    ("f", 8): 2,
    ("i", 8): 2,
    ("u", 8): 2,
    ("f", 4): 1,
    ("i", 4): 1,
    ("u", 4): 1,
    ("b", 1): 1,
}


def packable(arr: np.ndarray) -> bool:
    return arr.ndim == 1 and (arr.dtype.kind, arr.dtype.itemsize) in _WORDS


def pack_columns(
    arrays: Sequence[np.ndarray],
) -> tuple[np.ndarray, list[np.dtype]]:
    """Bit-cast typed columns into one [N, W] int32 word matrix (exact)."""
    n = len(arrays[0])
    spec = [a.dtype for a in arrays]
    words = []
    for a in arrays:
        w = _WORDS[(a.dtype.kind, a.dtype.itemsize)]
        if a.dtype.kind == "b":
            col = a.astype(np.int32).reshape(n, 1)
        else:
            col = (
                np.ascontiguousarray(a)
                .view(np.int32)
                .reshape(n, w)
            )
        words.append(col)
    return np.concatenate(words, axis=1) if words else np.zeros(
        (n, 0), np.int32
    ), spec


def unpack_columns(
    matrix: np.ndarray, spec: Sequence[np.dtype]
) -> list[np.ndarray]:
    """Inverse of pack_columns."""
    out = []
    ofs = 0
    for dt in spec:
        w = _WORDS[(dt.kind, dt.itemsize)]
        chunk = np.ascontiguousarray(matrix[:, ofs : ofs + w])
        if dt.kind == "b":
            out.append(chunk.reshape(-1).astype(bool))
        else:
            out.append(chunk.view(dt).reshape(-1))
        ofs += w
    return out


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _impl(n_shards: int, capacity: int, mesh: Any, axis: str):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def local(words, dst):
        # words: [per, W] i32; dst: [per] i32 (-1 = padding row)
        per, width = words.shape
        dstc = jnp.where(dst >= 0, dst, n_shards)  # padding sorts last
        order = jnp.argsort(dstc)  # stable
        swords = words[order]
        sdst = dstc[order]
        counts = jnp.bincount(sdst, length=n_shards + 1)
        starts = jnp.concatenate(
            [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]]
        )
        rank = jnp.arange(per) - starts[sdst]
        ok = (sdst < n_shards) & (rank < capacity)
        slot = jnp.where(ok, sdst * capacity + rank, n_shards * capacity)
        # scatter rows + a validity word into the send buffer (last slot is
        # the overflow/padding dump, sliced off before the collective)
        buf = jnp.zeros((n_shards * capacity + 1, width + 1), jnp.int32)
        payload = jnp.concatenate(
            [swords, ok.astype(jnp.int32)[:, None]], axis=1
        )
        buf = buf.at[slot].set(payload, mode="drop")
        send = buf[:-1].reshape(n_shards, capacity, width + 1)
        recv = jax.lax.all_to_all(
            send, axis, split_axis=0, concat_axis=0, tiled=False
        )
        return recv.reshape(n_shards * capacity, width + 1)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=P(axis, None),
        check_vma=False,
    )


@functools.lru_cache(maxsize=64)
def _jitted_impl(n_shards: int, capacity: int, mesh: Any, axis: str):
    import jax

    return jax.jit(_impl(n_shards, capacity, mesh, axis))


def ragged_all_to_all(
    words: np.ndarray,  # [N, W] int32 packed rows
    dest: np.ndarray,  # [N] int32 destination shard in [0, n_shards)
    mesh: Any,
    axis: str = "data",
    capacity: int | None = None,
) -> list[np.ndarray]:
    """Exchange rows to their destination shards through one device
    all-to-all. Returns, per destination shard, the [n_s, W] int32 word
    matrix of rows it received (order: by source shard, then source order).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_shards = mesh.shape[axis]
    n, width = words.shape
    per = _next_pow2(max(1, -(-n // n_shards)))
    total = per * n_shards
    if total > n:
        words = np.concatenate(
            [words, np.zeros((total - n, width), np.int32)]
        )
        dest = np.concatenate(
            [dest, np.full(total - n, -1, np.int32)]
        )
    src = np.arange(total) // per
    cnt = np.zeros((n_shards, n_shards), np.int64)
    valid = dest >= 0
    np.add.at(cnt, (src[valid], dest[valid]), 1)
    need = int(cnt.max())
    if capacity is None:
        capacity = _next_pow2(max(8, need))
    elif capacity < need:
        raise ValueError(
            f"capacity={capacity} would drop rows: a source shard sends "
            f"{need} rows to one destination"
        )
    capacity = min(capacity, per)

    dw = jax.device_put(
        jax.numpy.asarray(words), NamedSharding(mesh, P(axis, None))
    )
    dd = jax.device_put(
        jax.numpy.asarray(dest.astype(np.int32)),
        NamedSharding(mesh, P(axis)),
    )
    fn = _jitted_impl(n_shards, int(capacity), mesh, axis)
    out = np.asarray(fn(dw, dd))  # [n_shards * n_shards*capacity, W+1]
    out = out.reshape(n_shards, n_shards * capacity, width + 1)
    result = []
    for s in range(n_shards):
        block = out[s]
        rows = block[block[:, -1] == 1]
        result.append(np.ascontiguousarray(rows[:, :-1]))
    return result


def exchange_rows(
    arrays: Sequence[np.ndarray],
    dest: np.ndarray,
    mesh: Any,
    axis: str = "data",
) -> list[list[np.ndarray]]:
    """High-level Exchange: route typed columns to destination shards.
    Returns per-shard lists of typed column arrays (exact bit patterns)."""
    words, spec = pack_columns(list(arrays))
    blocks = ragged_all_to_all(
        words, dest.astype(np.int32), mesh, axis
    )
    return [unpack_columns(b, spec) for b in blocks]
