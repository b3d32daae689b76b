"""Host-side index objects backing the engine's ExternalIndexNode.

Replaces the reference's native index family (src/external_integration/):
- TpuDenseKnnIndex ← brute_force_knn_integration.rs + usearch_integration.rs
  (exact dense top-k on the MXU beats approximate HNSW on CPU at these sizes
  — the TPU-KNN result, arXiv 2206.14286)
- Bm25Index ← tantivy_integration.rs (host-side inverted index)
- LshKnnIndex ← stdlib/ml LSH candidate bucketing, projections on device
"""

from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Any, Sequence

import numpy as np

from pathway_tpu.engine.index_node import QUERY_DATA_ERRORS
from pathway_tpu.observability.tracing import get_tracer
from pathway_tpu.ops.knn import DeviceCorpus
from pathway_tpu.stdlib.indexing._filters import compile_filter


def _as_vector(data: Any) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return data.astype(np.float32, copy=False)
    return np.asarray(list(data), dtype=np.float32)


class TpuDenseKnnIndex:
    """Exact dense KNN with device-resident corpus; optional mesh sharding."""

    def __init__(
        self,
        dimensions: int | None = None,
        metric: str = "cosine",
        reserved_space: int = 1024,
        mesh: Any = None,
        axis: str = "data",
    ):
        self.dim = dimensions
        self.metric = metric
        self.reserved = reserved_space
        self.mesh = mesh
        self.axis = axis
        self.corpus: DeviceCorpus | None = None
        self.metadata: dict[int, Any] = {}
        self._m_occupancy: dict[int, Any] = {}  # labeled child per bucket

    def _ensure(self, dim: int) -> DeviceCorpus:
        if self.corpus is None:
            sharding = valid_sharding = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                sharding = NamedSharding(self.mesh, P(self.axis, None))
                valid_sharding = NamedSharding(self.mesh, P(self.axis))
            cap = self.reserved
            if self.mesh is not None:
                n_dev = self.mesh.shape[self.axis]
                cap = max(cap, n_dev)
                cap = ((cap + n_dev - 1) // n_dev) * n_dev
            self.corpus = DeviceCorpus(
                dim, cap, sharding=sharding, valid_sharding=valid_sharding
            )
        return self.corpus

    def upsert(self, key: int, data: Any, metadata: Any) -> None:
        vec = _as_vector(data)
        corpus = self._ensure(len(vec))
        corpus.upsert(key, vec)
        if metadata is not None:
            self.metadata[key] = metadata

    def remove(self, key: int) -> None:
        if self.corpus is not None:
            self.corpus.remove(key)
        self.metadata.pop(key, None)

    # --- shard-ownership support (Shard Harbor, serving/replica.py) -------

    def __len__(self) -> int:
        return 0 if self.corpus is None else len(self.corpus)

    def keys(self) -> list[int]:
        """Resident corpus row keys."""
        c = self.corpus
        return [] if c is None else list(c.slot_of.keys())

    def filter_keys(self, pred) -> None:
        """Keep only keys matching ``pred`` and COMPACT the backing
        buffers to the kept count — ``remove()`` frees slots but keeps
        the host/device arrays at their old capacity, which would erase
        the ~1/S per-member memory win a sharded replica hydrates for."""
        c = self.corpus
        if c is None:
            self.metadata = {k: v for k, v in self.metadata.items() if pred(k)}
            return
        kept = [(k, s) for k, s in c.slot_of.items() if pred(k)]
        fresh = DeviceCorpus(
            c.dim,
            max(len(kept), 1),
            sharding=c.sharding,
            valid_sharding=c.valid_sharding,
        )
        for key, slot in kept:
            fresh.upsert(key, c.host[slot])
        self.corpus = fresh
        self.metadata = {k: v for k, v in self.metadata.items() if pred(k)}

    def resident_bytes(self) -> int:
        """Host-side resident corpus bytes (the device mirror is the
        same shape) — the per-member memory evidence the shard×replica
        sweep records."""
        c = self.corpus
        if c is None:
            return 0
        return int(c.host.nbytes + c.valid_host.nbytes)

    # --- operator-snapshot support (reference: operator_snapshot.rs) ------
    # host-side content only; a restored mirror is uploaded whole, lazily

    def state_dict(self) -> dict:
        c = self.corpus
        return {
            "metadata": self.metadata,
            "corpus": None
            if c is None
            else {
                "dim": c.dim,
                "capacity": c.capacity,
                "host": c.host,
                "valid_host": c.valid_host,
                "free": list(c.free),
                "slot_of": dict(c.slot_of),
                "key_of": dict(c.key_of),
            },
        }

    def load_state(self, state: dict) -> None:
        self.metadata = dict(state["metadata"])
        cs = state["corpus"]
        self.corpus = None
        if cs is None:
            return
        c = self._ensure(cs["dim"])  # fresh corpus with current sharding
        if c.capacity == cs["capacity"]:
            c.host = cs["host"]
            c.valid_host = cs["valid_host"]
            c.free = list(cs["free"])
            c.slot_of = dict(cs["slot_of"])
            c.key_of = dict(cs["key_of"])
            c.mirror_replaced()
        else:  # capacity alignment changed between versions: re-upsert
            for key, slot in cs["slot_of"].items():
                c.upsert(key, cs["host"][slot])

    def search(self, queries: Sequence[tuple[Any, int, Any]]):
        if self.corpus is None or len(self.corpus) == 0 or not queries:
            return [() for _ in queries]
        with get_tracer().span(
            "index.search", queries=len(queries), rows=len(self.corpus)
        ) as span:
            return self._search(queries, span)

    def _search(self, queries: Sequence[tuple[Any, int, Any]], span: Any):
        """``search`` over a corpus that has rows, inside its span."""
        # host-side validation: everything a malformed query can break is
        # checked here, before any device work (engine/index_node.py
        # QUERY_DATA_ERRORS — recorded, answered empty)
        qmat = np.stack([_as_vector(q) for q, _k, _f in queries])
        if qmat.ndim != 2 or qmat.shape[1] != self.corpus.dim:
            raise ValueError(
                f"query vectors of shape {qmat.shape[1:]} against a "
                f"{self.corpus.dim}-dimensional corpus"
            )
        # the query batch is padded to the next power of two, so the
        # jitted top-k compiles once per bucket and not once per distinct
        # number of concurrent queries (the encoder does the same to its
        # batches)
        n_q = qmat.shape[0]
        bucket = 1 << max(0, n_q - 1).bit_length()
        if bucket != n_q:
            qmat = np.pad(qmat, ((0, bucket - n_q), (0, 0)))
        child = self._m_occupancy.get(bucket)
        if child is None:
            from pathway_tpu.serving.metrics import occupancy_histogram

            child = occupancy_histogram().labels("knn", str(bucket))
            self._m_occupancy[bucket] = child
        child.observe(n_q / bucket)
        max_k = max(int(k) for _q, k, _f in queries)
        has_filter = any(f is not None for _q, _k, f in queries)
        # oversample when filtering so post-filter still fills k
        eff_k = min(
            len(self.corpus), max_k * 4 if has_filter else max_k
        )
        span.set_attribute("bucket", bucket)
        span.set_attribute("k", eff_k)
        try:
            scores, idx = self.corpus.topk(qmat, eff_k, self.metric)
        except QUERY_DATA_ERRORS as exc:
            # the inputs passed the host checks above: a shape or
            # lowering complaint from here on is the device program's,
            # and must fail the tick instead of answering empty
            raise RuntimeError(
                f"device top-k failed to lower or run: {exc}"
            ) from exc
        scores = scores[:n_q].astype(np.float64)
        idx = idx[:n_q]
        if self.metric == "cosine":
            # reference USearch COS scores are -(1 - cos): negative
            # distances, not raw similarities
            scores = scores - 1.0
        out = []
        for qi, (_q, k, flt) in enumerate(queries):
            if int(k) <= 0:
                out.append(())  # k=0 means no matches, not one
                continue
            pred = compile_filter(flt) if flt else None
            matches = []
            for j in range(idx.shape[1]):
                slot = idx[qi, j]
                if slot < 0:
                    break
                key = self.corpus.key_of.get(int(slot))
                if key is None:
                    continue
                if pred is not None and not pred(self.metadata.get(key)):
                    continue
                matches.append((key, float(scores[qi, j])))
                if len(matches) >= int(k):
                    break
            out.append(tuple(matches))
        return out


_WORD = re.compile(r"\w+", re.UNICODE)


class Bm25Index:
    """BM25 full-text index (reference: tantivy_integration.rs:16)."""

    def __init__(self, k1: float = 1.2, b: float = 0.75):
        self.k1 = k1
        self.b = b
        self.docs: dict[int, dict[str, int]] = {}
        self.doc_len: dict[int, int] = {}
        self.postings: dict[str, dict[int, int]] = defaultdict(dict)
        self.metadata: dict[int, Any] = {}

    @staticmethod
    def _tokens(text: str) -> list[str]:
        return [w.lower() for w in _WORD.findall(str(text))]

    def upsert(self, key: int, data: Any, metadata: Any) -> None:
        self.remove(key)
        tf: dict[str, int] = defaultdict(int)
        toks = self._tokens(data)
        for tok in toks:
            tf[tok] += 1
        self.docs[key] = dict(tf)
        self.doc_len[key] = len(toks)
        for tok, c in tf.items():
            self.postings[tok][key] = c
        if metadata is not None:
            self.metadata[key] = metadata

    def remove(self, key: int) -> None:
        tf = self.docs.pop(key, None)
        if tf:
            for tok in tf:
                self.postings[tok].pop(key, None)
        self.doc_len.pop(key, None)
        self.metadata.pop(key, None)

    def search(self, queries: Sequence[tuple[Any, int, Any]]):
        n = len(self.docs)
        if n == 0:
            return [() for _ in queries]
        avg_len = sum(self.doc_len.values()) / n
        out = []
        for qtext, k, flt in queries:
            pred = compile_filter(flt) if flt else None
            scores: dict[int, float] = defaultdict(float)
            for tok in self._tokens(qtext):
                plist = self.postings.get(tok)
                if not plist:
                    continue
                idf = math.log(1 + (n - len(plist) + 0.5) / (len(plist) + 0.5))
                for doc, tf in plist.items():
                    dl = self.doc_len[doc] or 1
                    scores[doc] += (
                        idf
                        * tf
                        * (self.k1 + 1)
                        / (tf + self.k1 * (1 - self.b + self.b * dl / avg_len))
                    )
            ranked = sorted(scores.items(), key=lambda kv: -kv[1])
            matches = []
            for doc, s in ranked:
                if pred is not None and not pred(self.metadata.get(doc)):
                    continue
                matches.append((doc, float(s)))
                if len(matches) >= int(k):
                    break
            out.append(tuple(matches))
        return out


class LshKnnIndex:
    """LSH-bucketed ANN: device projections pick candidate buckets, exact
    rerank within candidates (reference: stdlib/ml/classifiers/_lsh.py)."""

    def __init__(
        self,
        dimensions: int,
        n_or: int = 8,
        n_and: int = 4,
        bucket_length: float = 4.0,
        metric: str = "l2sq",
        seed: int = 42,
    ):
        from pathway_tpu.ops.lsh import make_projections

        self.dim = dimensions
        self.n_or = n_or
        self.bucket_length = bucket_length
        self.metric = metric
        self.planes, self.offsets = make_projections(
            dimensions, n_or, n_and, bucket_length, seed
        )
        self.buckets: list[dict[int, set[int]]] = [
            defaultdict(set) for _ in range(n_or)
        ]
        self.vectors: dict[int, np.ndarray] = {}
        self.metadata: dict[int, Any] = {}

    def _bucket_ids(self, vecs: np.ndarray) -> np.ndarray:
        from pathway_tpu.ops.lsh import lsh_buckets

        return np.asarray(
            lsh_buckets(vecs, self.planes, self.offsets, self.bucket_length)
        )

    def upsert(self, key: int, data: Any, metadata: Any) -> None:
        vec = _as_vector(data)
        self.remove(key)
        self.vectors[key] = vec
        ids = self._bucket_ids(vec[None])[0]
        for t, b in enumerate(ids):
            self.buckets[t][int(b)].add(key)
        if metadata is not None:
            self.metadata[key] = metadata

    def remove(self, key: int) -> None:
        vec = self.vectors.pop(key, None)
        if vec is not None:
            ids = self._bucket_ids(vec[None])[0]
            for t, b in enumerate(ids):
                self.buckets[t][int(b)].discard(key)
        self.metadata.pop(key, None)

    def state_dict(self) -> dict:
        # planes/offsets are deterministic from the constructor args, so
        # only the mutable content snapshots (jax arrays stay out)
        return {
            "buckets": [dict(b) for b in self.buckets],
            "vectors": self.vectors,
            "metadata": self.metadata,
        }

    def load_state(self, state: dict) -> None:
        self.buckets = [defaultdict(set, b) for b in state["buckets"]]
        self.vectors = dict(state["vectors"])
        self.metadata = dict(state["metadata"])

    def search(self, queries: Sequence[tuple[Any, int, Any]]):
        if not self.vectors:
            return [() for _ in queries]
        qmat = np.stack([_as_vector(q) for q, _k, _f in queries])
        all_ids = self._bucket_ids(qmat)
        out = []
        for qi, (q, k, flt) in enumerate(queries):
            pred = compile_filter(flt) if flt else None
            candidates: set[int] = set()
            for t, b in enumerate(all_ids[qi]):
                candidates |= self.buckets[t].get(int(b), set())
            if not candidates:
                out.append(())
                continue
            qv = _as_vector(q)
            scored = []
            for key in candidates:
                if pred is not None and not pred(self.metadata.get(key)):
                    continue
                v = self.vectors[key]
                if self.metric == "cosine":
                    # same convention as the dense backends: negative
                    # cosine distance (cos - 1), exact match scores 0
                    s = float(
                        np.dot(qv, v)
                        / ((np.linalg.norm(qv) * np.linalg.norm(v)) + 1e-30)
                    ) - 1.0
                else:
                    s = -float(np.sum((qv - v) ** 2))
                scored.append((key, s))
            scored.sort(key=lambda kv: -kv[1])
            out.append(tuple(scored[: int(k)]))
        return out


class IvfKnnIndex:
    """Two-level IVF KNN — the >HBM scale-out tier (design note in
    ops/ivf.py; reference counterpart: usearch HNSW,
    src/external_integration/usearch_integration.rs:20). Coarse matmul
    quantization picks nprobe inverted lists, exact matmul scoring ranks
    their members. Below ``min_train`` points (and until training) the
    index scores exactly over everything, so small corpora behave
    identically to the brute-force index."""

    def __init__(
        self,
        dimensions: int | None = None,
        metric: str = "cosine",
        n_clusters: int | None = None,
        n_probe: int | None = None,
        min_train: int = 4096,
        train_sample: int = 20000,
        seed: int = 0,
    ):
        if metric not in ("cosine", "dot", "l2sq"):
            raise ValueError(f"unknown metric {metric!r}")
        self.dim = dimensions
        self.metric = metric
        self.n_clusters = n_clusters
        self.n_probe = n_probe
        self.min_train = min_train
        self.train_sample = train_sample
        self.seed = seed
        self.vecs: dict[int, np.ndarray] = {}
        self.metadata: dict[int, Any] = {}
        self.centroids: np.ndarray | None = None
        self.lists: dict[int, set[int]] = {}
        self.key_cluster: dict[int, int] = {}
        self._pending: list[int] = []  # keys awaiting cluster assignment
        self._trained_size = 0

    # --- maintenance ------------------------------------------------------

    def _space(self, v: np.ndarray) -> np.ndarray:
        """Clustering space: normalized for cosine (so L2 ~ angle), raw
        otherwise."""
        if self.metric == "cosine":
            return v / (np.linalg.norm(v, axis=-1, keepdims=True) + 1e-30)
        return v

    def upsert(self, key: int, data: Any, metadata: Any) -> None:
        vec = _as_vector(data)
        if self.dim is not None and len(vec) != self.dim:
            raise ValueError(
                f"IvfKnnIndex: expected {self.dim}-dim vectors, "
                f"got {len(vec)}"
            )
        self.remove(key)
        self.vecs[key] = vec
        if metadata is not None:
            self.metadata[key] = metadata
        self._pending.append(key)

    def remove(self, key: int) -> None:
        self.vecs.pop(key, None)
        self.metadata.pop(key, None)
        c = self.key_cluster.pop(key, None)
        if c is not None:
            self.lists.get(c, set()).discard(key)

    def _maybe_train(self) -> None:
        from pathway_tpu.ops.ivf import train_centroids

        n = len(self.vecs)
        if n < self.min_train:
            return
        if self.centroids is not None and n < 4 * self._trained_size:
            return
        rng = np.random.default_rng(self.seed)
        keys = list(self.vecs.keys())
        if len(keys) > self.train_sample:
            keys = [
                keys[i]
                for i in rng.choice(
                    len(keys), size=self.train_sample, replace=False
                )
            ]
        sample = self._space(np.stack([self.vecs[k] for k in keys]))
        n_clusters = self.n_clusters or max(
            8, int(round(math.sqrt(n) / 8)) * 8
        )
        self.centroids = train_centroids(
            sample, n_clusters, seed=self.seed
        )
        # reassign EVERYTHING under the new centroids
        self.lists = {}
        self.key_cluster = {}
        self._pending = list(self.vecs.keys())
        self._trained_size = n

    def _flush_assign(self) -> None:
        from pathway_tpu.ops.ivf import assign_clusters

        if self.centroids is None:
            return  # keep pending until training happens
        if not self._pending:
            return
        keys = [k for k in self._pending if k in self.vecs]
        self._pending = []
        if not keys:
            return
        x = self._space(np.stack([self.vecs[k] for k in keys]))
        assign = assign_clusters(x, self.centroids)
        for k, c in zip(keys, assign.tolist()):
            self.key_cluster[k] = c
            self.lists.setdefault(c, set()).add(k)

    # --- snapshots --------------------------------------------------------

    def state_dict(self) -> dict:
        return {
            "vecs": self.vecs,
            "metadata": self.metadata,
            "centroids": self.centroids,
            "key_cluster": self.key_cluster,
            "trained_size": self._trained_size,
        }

    def load_state(self, state: dict) -> None:
        self.vecs = dict(state["vecs"])
        self.metadata = dict(state["metadata"])
        self.centroids = state["centroids"]
        self._trained_size = int(state.get("trained_size", 0))
        self.key_cluster = dict(state["key_cluster"])
        self.lists = {}
        for k, c in self.key_cluster.items():
            self.lists.setdefault(c, set()).add(k)
        self._pending = [k for k in self.vecs if k not in self.key_cluster]

    # --- query ------------------------------------------------------------

    def _score(self, q: np.ndarray, keys: list[int]) -> np.ndarray:
        mat = np.stack([self.vecs[k] for k in keys]).astype(np.float32)
        qv = q.astype(np.float32)
        if self.metric == "cosine":
            qv = qv / (np.linalg.norm(qv) + 1e-30)
            mat = mat / (
                np.linalg.norm(mat, axis=1, keepdims=True) + 1e-30
            )
            return mat @ qv - 1.0  # reference COS convention: -(1 - cos)
        if self.metric == "l2sq":
            d = mat - qv[None, :]
            return -np.sum(d * d, axis=1)
        return mat @ qv

    def search(self, queries: Sequence[tuple[Any, int, Any]]):
        if not queries:
            return []
        if not self.vecs:
            return [() for _ in queries]
        self._maybe_train()
        self._flush_assign()
        out = []
        for q, k, flt in queries:
            if int(k) <= 0:
                out.append(())
                continue
            qv = _as_vector(q)
            if self.centroids is None:
                cand = list(self.vecs.keys())  # exact below min_train
            else:
                qs = self._space(qv[None, :]).astype(np.float32)
                c32 = self.centroids.astype(np.float32)
                d = (
                    np.sum(c32 * c32, axis=1)
                    - 2.0 * (qs @ c32.T)[0]
                )
                n_probe = self.n_probe or max(
                    1, int(round(math.sqrt(len(c32))))
                )
                n_probe = min(n_probe, len(c32))
                probes = np.argpartition(d, n_probe - 1)[:n_probe]
                cand = [
                    key
                    for c in probes.tolist()
                    for key in self.lists.get(c, ())
                ]
                if not cand:
                    cand = list(self.vecs.keys())
            scores = self._score(qv, cand)
            order = np.argsort(-scores, kind="stable")
            pred = compile_filter(flt) if flt else None
            matches = []
            for j in order.tolist():
                key = cand[j]
                if pred is not None and not pred(self.metadata.get(key)):
                    continue
                matches.append((key, float(scores[j])))
                if len(matches) >= int(k):
                    break
            out.append(tuple(matches))
        return out
