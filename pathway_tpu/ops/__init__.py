"""pathway_tpu.ops — jitted XLA/Pallas kernels for the engine's hot paths.

This package is the TPU-native replacement for the reference's native
compute: ndarray matmul (src/mat_mul.rs), the external index family
(src/external_integration/ — USearch HNSW / brute-force KNN / Tantivy BM25)
and the per-row expression interpreter's heavy numeric ops. Everything here is
pure jax — jit once, run per microbatch tick.
"""

from pathway_tpu.ops.knn import dense_topk, sharded_topk
from pathway_tpu.ops.segment import segment_count, segment_mean, segment_sum

__all__ = [
    "dense_topk",
    "sharded_topk",
    "segment_sum",
    "segment_count",
    "segment_mean",
]
