"""IVF (inverted-file) KNN kernels — the scale-out story past HBM-resident
brute force.

Design note: the reference carries usearch HNSW for
sub-linear queries (reference: src/external_integration/
usearch_integration.rs:20). HNSW is a pointer-chasing CPU structure — the
worst possible shape for a TPU. The TPU-native answer is IVF: both of its
stages are MXU matmuls,

  1. coarse quantization: queries x centroids^T  -> top-nprobe clusters
  2. fine scoring:        queries x members^T    -> exact top-k within
     the probed inverted lists

so query cost is O(C·D + (N/C)·nprobe·D) instead of O(N·D), with every
FLOP on the systolic array and no data-dependent pointer walks. Training
is mini-batch Lloyd over a sample — also pure matmuls. For corpora that
fit HBM the exact dense path stays faster (TPU-KNN, arXiv 2206.14286);
IVF is the >HBM / sub-linear tier behind the same DataIndex factory
surface.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=())
def _assign_impl(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest centroid per row by L2: argmin ||x - c||^2 via the matmul
    expansion (x·c dominates; norms are rank-1 corrections)."""
    x32 = x.astype(jnp.float32)
    c32 = centroids.astype(jnp.float32)
    dots = x32 @ c32.T  # [n, C] — the MXU stage
    c2 = jnp.sum(c32 * c32, axis=1)
    return jnp.argmin(c2[None, :] - 2.0 * dots, axis=1)


def assign_clusters(x: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Cluster id per row. Pads the row count to the next power of two so
    jit caches stay bounded while batch sizes vary."""
    n = x.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    pad = 1
    while pad < n:
        pad *= 2
    if pad != n:
        x = np.concatenate([x, np.zeros((pad - n, x.shape[1]), x.dtype)])
    out = np.asarray(_assign_impl(jnp.asarray(x), jnp.asarray(centroids)))
    return out[:n].astype(np.int64)


def train_centroids(
    sample: np.ndarray,
    n_clusters: int,
    n_iters: int = 10,
    seed: int = 0,
) -> np.ndarray:
    """Lloyd's k-means on a sample: random-subset init, matmul assignment,
    segment-sum update. Empty clusters re-seed from random points."""
    rng = np.random.default_rng(seed)
    n = sample.shape[0]
    n_clusters = min(n_clusters, n)
    centroids = sample[rng.choice(n, size=n_clusters, replace=False)].astype(
        np.float32
    )
    for _ in range(n_iters):
        assign = assign_clusters(sample, centroids)
        sums = np.zeros_like(centroids)
        np.add.at(sums, assign, sample.astype(np.float32))
        counts = np.bincount(assign, minlength=n_clusters).astype(np.float32)
        empty = counts == 0
        counts[empty] = 1.0
        centroids = sums / counts[:, None]
        if empty.any():
            centroids[empty] = sample[
                rng.choice(n, size=int(empty.sum()), replace=False)
            ]
    return centroids


class IvfDeviceIndex:
    """Device-resident IVF for corpora where brute force is too slow:
    the corpus is permuted into cluster-sorted order at build time, so a
    probed cluster is ONE contiguous HBM range — queries gather nprobe
    ranges, pad to a bucketed static length, and run one fine-scoring
    matmul + top-k per bucket size (static shapes: no recompiles beyond
    the handful of buckets). Both stages are MXU matmuls; there are no
    data-dependent pointer walks (design note at module top; reference
    counterpart: usearch HNSW, usearch_integration.rs:20).

    ``spill`` stores each point in its `spill` nearest lists (ScaNN-style
    multi-assignment): boundary points — where IVF loses its recall on
    unstructured data — then appear in every nearby probe, trading `spill`x
    index memory for recall at fixed n_probe.
    """

    def __init__(
        self,
        corpus: np.ndarray,
        metric: str = "cosine",
        n_clusters: int | None = None,
        n_probe: int | None = None,
        spill: int = 2,
        train_sample: int = 40000,
        seed: int = 0,
    ):
        if metric not in ("cosine", "dot"):
            raise ValueError(f"IvfDeviceIndex: unsupported metric {metric!r}")
        n, dim = corpus.shape
        self.metric = metric
        self.n = n
        self.n_clusters = n_clusters or max(8, int(round((n**0.5) / 8)) * 8)
        self.n_probe = n_probe or max(1, int(round(self.n_clusters**0.5)))
        rng = np.random.default_rng(seed)
        x = corpus.astype(np.float32)
        if metric == "cosine":
            x = x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-30)
        sample = x[rng.choice(n, size=min(train_sample, n), replace=False)]
        self.centroids = train_centroids(sample, self.n_clusters, seed=seed)
        # batched multi-assignment (each point -> its `spill` nearest
        # centroids), then cluster-sort the replicated corpus
        spill = max(1, min(spill, self.n_clusters))
        self.spill = spill
        assign = np.empty((n, spill), np.int32)
        step = 262_144
        cT = self.centroids.T.astype(np.float32)
        c2 = np.sum(self.centroids.astype(np.float32) ** 2, axis=1)
        for lo in range(0, n, step):
            xs = x[lo : lo + step]
            d = c2[None, :] - 2.0 * (xs @ cT)  # ||c||^2 - 2 x.c (+||x||^2)
            assign[lo : lo + step] = np.argpartition(d, spill - 1, axis=1)[
                :, :spill
            ]
        flat_assign = assign.ravel()
        point_of = np.repeat(np.arange(n, dtype=np.int64), spill)
        perm = np.argsort(flat_assign, kind="stable")
        self.order = point_of[perm]
        sorted_assign = flat_assign[perm]
        self.starts = np.searchsorted(
            sorted_assign, np.arange(self.n_clusters)
        ).astype(np.int64)
        self.ends = np.searchsorted(
            sorted_assign, np.arange(self.n_clusters), side="right"
        ).astype(np.int64)
        self.corpus_dev = jax.device_put(x[self.order])
        self.cent_dev = jax.device_put(self.centroids)
        self._fine = {}  # bucket size -> jitted fine scorer

    def _fine_fn(self, bucket: int):
        fn = self._fine.get(bucket)
        if fn is None:

            def fine(q, idx, valid, k):
                rows = jnp.take(self.corpus_dev, idx, axis=0)
                scores = rows @ q
                scores = jnp.where(valid, scores, -jnp.inf)
                top_s, top_i = jax.lax.top_k(scores, k)
                return top_s, jnp.take(idx, top_i)

            fn = jax.jit(fine, static_argnames=("k",))
            self._fine[bucket] = fn
        return fn

    def query(self, q: np.ndarray, k: int = 10) -> tuple[np.ndarray, np.ndarray]:
        """Top-k (scores, original corpus ids) for one query vector."""
        qv = q.astype(np.float32)
        if self.metric == "cosine":
            qv = qv / (np.linalg.norm(qv) + 1e-30)
        d = self.centroids @ qv
        probes = np.argpartition(-d, self.n_probe - 1)[: self.n_probe]
        spans = [(self.starts[c], self.ends[c]) for c in probes.tolist()]
        # dedupe spilled replicas BY POINT id, or duplicates crowd out
        # top-k slots; keep the first sorted position per point
        pos_all = np.concatenate(
            [np.arange(s, e) for s, e in spans]
        ) if spans else np.zeros(0, np.int64)
        pts = self.order[pos_all]
        _uniq, first = np.unique(pts, return_index=True)
        pos_u = pos_all[first]
        total = len(pos_u)
        bucket = 1 << max(1, (total - 1)).bit_length()  # next power of 2
        idx = np.zeros(bucket, np.int64)
        valid = np.zeros(bucket, bool)
        idx[:total] = pos_u
        valid[:total] = True
        kk = min(k, bucket)  # lax.top_k needs k <= operand length
        top_s, top_pos = self._fine_fn(bucket)(
            jax.device_put(qv), jax.device_put(idx), jax.device_put(valid), kk
        )
        top_s = np.asarray(top_s)
        ids = self.order[np.asarray(top_pos)]
        live = top_s > -np.inf  # drop padding slots when total < k
        return top_s[live], ids[live]
