"""Dense top-k KNN on TPU — the MXU-native replacement for the reference's
external index family (reference: src/external_integration/
brute_force_knn_integration.rs:22 ndarray matmul top-k, and
usearch_integration.rs HNSW; pattern: TPU-KNN, arXiv 2206.14286).

Design:
- corpus lives in HBM as a padded [capacity, D] array (+ validity mask) so
  shapes stay static across ticks — no recompilation as documents stream in;
  capacity grows by doubling (each size compiles once).
- scores = queries @ corpus.T runs in bfloat16 on the MXU with f32
  accumulation; invalid slots are masked to -inf before `lax.top_k`.
- multi-chip: corpus rows are sharded over the mesh's 'data' axis via
  shard_map — each device computes a local top-k, candidates are
  all-gathered over ICI and merged with a final top-k (the TPU-KNN
  recall@peak-FLOPs recipe).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from pathway_tpu.observability.tracing import NOOP_SPAN, get_tracer


@dataclass(frozen=True)
class KnnParams:
    metric: str = "cosine"  # cosine | dot | l2sq
    bf16: bool = True


# The jax.named_scope names below are op metadata only (nothing computed
# changes): a profiler capture shows the scan, the top-k and the corpus
# preparation under these names instead of XLA's generated ones.


@jax.named_scope("knn.scores")
def _scores(
    queries: jax.Array, corpus: jax.Array, metric: str, bf16: bool
) -> jax.Array:
    if metric == "cosine":
        qn = queries / (
            jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-30
        )
        cn = corpus / (jnp.linalg.norm(corpus, axis=-1, keepdims=True) + 1e-30)
    else:
        qn, cn = queries, corpus
    if bf16:
        qn = qn.astype(jnp.bfloat16)
        cn = cn.astype(jnp.bfloat16)
    dots = jax.lax.dot_general(
        qn,
        cn,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if metric == "l2sq":
        q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        c2 = jnp.sum(corpus.astype(jnp.float32) ** 2, axis=-1)
        # negative squared distance so that bigger == closer
        return -(q2 - 2.0 * dots + c2[None, :])
    return dots


@jax.named_scope("knn.topk")
def _masked_topk(s: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over [B, N] scores. For large N uses the two-stage
    block decomposition (top-k per 1024-column block, then top-k over the
    block winners) — exact because every global top-k element is within
    the top-k of its own block, and much friendlier to the TPU than one
    monolithic 1M-wide TopK."""
    n = s.shape[-1]
    blk = 1024
    if n >= 64 * blk and k <= blk:
        nblk = (n + blk - 1) // blk
        pad = nblk * blk - n
        if pad:
            s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        sb = s.reshape(s.shape[0], nblk, blk)
        sc1, ix1 = jax.lax.top_k(sb, k)  # [B, nblk, k]
        gidx = ix1 + (jnp.arange(nblk, dtype=ix1.dtype) * blk)[None, :, None]
        sc2, pos = jax.lax.top_k(sc1.reshape(s.shape[0], -1), k)
        idx = jnp.take_along_axis(gidx.reshape(s.shape[0], -1), pos, axis=1)
        return sc2, idx
    return jax.lax.top_k(s, k)


@functools.partial(jax.jit, static_argnames=("k", "metric", "bf16"))
def dense_topk(
    queries: jax.Array,  # [B, D] f32
    corpus: jax.Array,  # [N, D] f32 (padded)
    valid: jax.Array,  # [N] bool
    k: int,
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores [B, k] f32, indices [B, k] i32); invalid rows get
    -inf scores and index -1."""
    s = _scores(queries, corpus, metric, bf16)
    s = jnp.where(valid[None, :], s, -jnp.inf)
    scores, idx = _masked_topk(s, k)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx


# --- prepared-corpus fast path ---------------------------------------------
# Normalization + bf16 cast of the corpus is O(N*D) — done once per corpus
# change, NOT per query. Per-query work is one [B,D]x[D,N] MXU matmul + topk.


@functools.partial(jax.jit, static_argnames=("metric", "bf16"))
@jax.named_scope("corpus.prepare")
def prepare_corpus(corpus: jax.Array, metric: str, bf16: bool = True):
    """Returns (prep [N,D], c2 [N]) — prep is normalized (cosine) and cast;
    c2 is the squared-norm column needed by l2sq."""
    c2 = jnp.sum(corpus.astype(jnp.float32) ** 2, axis=-1)
    if metric == "cosine":
        prep = corpus / (jnp.linalg.norm(corpus, axis=-1, keepdims=True) + 1e-30)
    else:
        prep = corpus
    if bf16:
        prep = prep.astype(jnp.bfloat16)
    return prep, c2


@functools.partial(jax.jit, static_argnames=("k", "metric", "bf16"))
def dense_topk_prepared(
    queries: jax.Array,  # [B, D] f32
    prep: jax.Array,  # [N, D] prepared (normalized/cast)
    c2: jax.Array,  # [N] squared norms (l2sq only)
    valid: jax.Array,  # [N] bool
    k: int,
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[jax.Array, jax.Array]:
    with jax.named_scope("knn.scores"):
        if metric == "cosine":
            q = queries / (
                jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-30
            )
        else:
            q = queries
        if bf16:
            q = q.astype(jnp.bfloat16)
        dots = jax.lax.dot_general(
            q,
            prep,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if metric == "l2sq":
            q2 = jnp.sum(
                queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True
            )
            s = -(q2 - 2.0 * dots + c2[None, :])
        else:
            s = dots
        s = jnp.where(valid[None, :], s, -jnp.inf)
    scores, idx = _masked_topk(s, k)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx


def cosine_topk(queries, corpus, valid, k):
    return dense_topk(queries, corpus, valid, k, metric="cosine")


def shard_base_indices(n: int, n_shards: int) -> np.ndarray:
    """Per-row base offset of its shard (local->global index mapping in the
    sharded merge); single source for sharded_topk and the multi-process
    sharded_topk_global."""
    per = n // n_shards
    return (np.arange(n) // per * per).astype(np.int32)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "bf16", "mesh", "axis")
)
def _sharded_topk_impl(queries, corpus, valid, base_idx, k, metric, bf16, mesh, axis):
    from jax.sharding import PartitionSpec as P

    def local(q, c, v, b):
        s = _scores(q, c, metric, bf16)
        s = jnp.where(v[None, :], s, -jnp.inf)
        kk = min(k, c.shape[0])
        sc, ix = _masked_topk(s, kk)
        ix = ix + b[0]  # local -> global row index
        # gather candidates from all shards over ICI, merge with final top-k
        sc_all = jax.lax.all_gather(sc, axis, axis=1, tiled=True)
        ix_all = jax.lax.all_gather(ix, axis, axis=1, tiled=True)
        sc_f, pos = jax.lax.top_k(sc_all, k)
        ix_f = jnp.take_along_axis(ix_all, pos, axis=1)
        ix_f = jnp.where(jnp.isfinite(sc_f), ix_f, -1)
        return sc_f, ix_f

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, corpus, valid, base_idx)


def sharded_topk(
    queries: jax.Array,
    corpus: jax.Array,
    valid: jax.Array,
    k: int,
    *,
    mesh: Any,
    axis: str = "data",
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Multi-chip KNN: corpus sharded over ``axis``; queries replicated;
    local top-k per shard + all-gather merge (TPU-KNN pattern)."""
    n = corpus.shape[0]
    n_shards = mesh.shape[axis]
    assert n % n_shards == 0, "pad corpus to a multiple of the shard count"
    base_idx = shard_base_indices(n, n_shards)
    return _sharded_topk_impl(
        queries, corpus, valid, jnp.asarray(base_idx), k, metric, bf16, mesh, axis
    )


def _ready_if_live(span: Any, arrays: Any) -> None:
    """A span around a transfer or a program is truthful only if it ends
    when the device has the data (``jnp.asarray`` of 2.4 GB returns after
    0.5 ms, the data arrive after 230), so a live span waits for what it
    produced. The search that caused the refresh waits for the same arrays
    before it can answer, but each wait is a round trip of its own: on a
    v5e the two cost 6 ms of a 254 ms refresh (PERF.md section 6, PR 26).
    A disabled tracer's shared no-op span syncs nothing."""
    if span is not NOOP_SPAN:
        jax.block_until_ready(arrays)


class DeviceCorpus:
    """Growable padded corpus living on device.

    Host keeps a float32 mirror; the device array is refreshed lazily per
    tick (one host→device transfer per changed tick, amortized over all
    queries in that tick). Capacity doubles ⇒ O(log N) distinct compiled
    shapes."""

    def __init__(
        self,
        dim: int,
        capacity: int = 1024,
        sharding: Any = None,
        valid_sharding: Any = None,
    ):
        self.valid_sharding = valid_sharding
        self.dim = dim
        # align capacity to lcm(1024, n_shards): multiple of 1024 so the
        # Pallas block kernel (ops/pallas_topk.py, BLK=1024) is always
        # applicable, AND divisible by the mesh shard count so sharded_topk
        # can split rows evenly; padding is masked by `valid`
        align = 1024
        if sharding is not None:
            import math

            n_dev = int(np.prod(list(sharding.mesh.shape.values())))
            align = math.lcm(1024, max(1, n_dev))
        self._align = align
        self.capacity = -(-max(1024, capacity) // align) * align
        self.host = np.zeros((self.capacity, dim), dtype=np.float32)
        self.valid_host = np.zeros(self.capacity, dtype=bool)
        self.free: list[int] = list(range(self.capacity - 1, -1, -1))
        self.slot_of: dict[int, int] = {}  # row key -> slot
        self.key_of: dict[int, int] = {}  # slot -> row key
        self._dirty = True
        self._device: jax.Array | None = None
        self._device_valid: jax.Array | None = None
        self._prepared: dict[tuple[str, bool], tuple[jax.Array, jax.Array]] = {}
        self.sharding = sharding

    def __len__(self) -> int:
        return len(self.slot_of)

    def upsert(self, key: int, vector: np.ndarray) -> None:
        slot = self.slot_of.get(key)
        if slot is None:
            if not self.free:
                self._grow()
            slot = self.free.pop()
            self.slot_of[key] = slot
            self.key_of[slot] = key
        self.host[slot] = vector
        self.valid_host[slot] = True
        self._dirty = True

    def remove(self, key: int) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is None:
            return
        self.key_of.pop(slot, None)
        self.valid_host[slot] = False
        self.free.append(slot)
        self._dirty = True

    def _grow(self) -> None:
        old_cap = self.capacity
        self.capacity *= 2
        host = np.zeros((self.capacity, self.dim), dtype=np.float32)
        host[:old_cap] = self.host
        self.host = host
        valid = np.zeros(self.capacity, dtype=bool)
        valid[:old_cap] = self.valid_host
        self.valid_host = valid
        self.free.extend(range(self.capacity - 1, old_cap - 1, -1))
        self._dirty = True

    def device_arrays(self) -> tuple[jax.Array, jax.Array]:
        if self._dirty or self._device is None:
            with get_tracer().span(
                "corpus.upload",
                bytes=self.host.nbytes + self.valid_host.nbytes,
                rows=len(self),
            ) as span:
                if self.sharding is not None:
                    self._device = jax.device_put(self.host, self.sharding)
                    self._device_valid = jax.device_put(
                        self.valid_host, self.valid_sharding
                    )
                else:
                    self._device = jnp.asarray(self.host)
                    self._device_valid = jnp.asarray(self.valid_host)
                _ready_if_live(span, (self._device, self._device_valid))
            self._prepared.clear()
            self._dirty = False
        return self._device, self._device_valid

    def prepared_arrays(
        self, metric: str, bf16: bool = True
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """(prep, c2, valid) with normalization/cast amortized across
        queries — refreshed only when the corpus changed."""
        device, valid = self.device_arrays()
        key = (metric, bf16)
        if key not in self._prepared:
            with get_tracer().span(
                "corpus.prepare", metric=metric, bf16=bf16, rows=len(self)
            ) as span:
                self._prepared[key] = prepare_corpus(device, metric, bf16)
                _ready_if_live(span, self._prepared[key])
        prep, c2 = self._prepared[key]
        return prep, c2, valid

    def keys_for_slots(self, slots: np.ndarray) -> list[int | None]:
        return [
            self.key_of.get(int(s)) if s >= 0 else None for s in slots
        ]
