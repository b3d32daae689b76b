"""Multi-process execution — the DCN/multi-host rung of the comm backend.

The reference scales past one process by spawning PATHWAY_PROCESSES OS
processes connected by timely's TCP mesh (reference:
src/engine/dataflow/config.rs:88-121, env contract
PATHWAY_PROCESSES/PATHWAY_PROCESS_ID/PATHWAY_FIRST_PORT;
external/timely-dataflow/communication/src/networking.rs:16-33). The
TPU-native equivalent is one JAX process per host joined through
``jax.distributed``: after initialization every process sees the global
device set, meshes span hosts, and XLA collectives ride ICI within a slice
and DCN across slices — no hand-rolled socket protocol.

What runs multi-process: device-resident data parallelism — corpus
sharding for the KNN/retrieval path (`sharded_topk_global`), embed batch
sharding — on the jax.distributed device group (this module), and host-
side keyed engine state spanning processes over the TCP host mesh
(parallel/host_exchange.py + engine/dcn.py): groupby/join state is
key-sharded across the process group with lockstep barrier-scheduled
ticks and group-consistent persistence. The device group is joined when
PATHWAY_JAX_DISTRIBUTED=1; the host mesh joins whenever
PATHWAY_PROCESSES > 1.
"""

from __future__ import annotations

import os
from typing import Any

import numpy as np

_initialized = False


def process_env() -> tuple[int, int, str]:
    """(num_processes, process_id, coordinator) from the reference env
    contract; coordinator defaults to localhost at PATHWAY_FIRST_PORT."""
    n = int(os.environ.get("PATHWAY_PROCESSES", "1") or 1)
    pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0") or 0)
    first_port = int(os.environ.get("PATHWAY_FIRST_PORT", "10000") or 10000)
    coord = os.environ.get(
        "JAX_COORDINATOR_ADDRESS", f"127.0.0.1:{first_port}"
    )
    return n, pid, coord


def maybe_initialize() -> bool:
    """Join the process group when PATHWAY_PROCESSES > 1 (idempotent).
    Returns True when running multi-process. On the CPU backend jax's
    default collectives implementation (gloo) carries the cross-process
    collectives of the tests and the CPU dry run."""
    global _initialized
    n, pid, coord = process_env()
    if n <= 1:
        return False
    if _initialized:
        return True
    import jax

    try:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=n, process_id=pid
        )
    except RuntimeError as e:
        # most common cause: user code ran a JAX computation during graph
        # construction, initializing backends before pw.run() could join
        # the process group
        raise RuntimeError(
            f"PATHWAY_PROCESSES={n} but the JAX process group could not be "
            "joined. jax.distributed.initialize must run before any JAX "
            "computation — avoid touching JAX arrays while declaring the "
            "graph, or call pathway_tpu.parallel.distributed."
            "maybe_initialize() at the top of your script "
            f"(original error: {e})"
        ) from e
    _initialized = True
    return True


def is_multiprocess() -> bool:
    import jax

    return _initialized or jax.process_count() > 1


def global_mesh(axis: str = "data"):
    """Mesh over the GLOBAL device set (all processes)."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis,))


def from_process_local(local: np.ndarray, mesh: Any, axis: str = "data"):
    """Assemble a globally-sharded array from each process's local rows
    (the multi-host replacement for device_put-with-sharding, which
    requires the full array on every host)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(axis, *([None] * (local.ndim - 1)))),
        jnp.asarray(local),
    )


def replicated(value: np.ndarray, mesh: Any):
    """A fully-replicated global array (every process passes equal data)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P()), jnp.asarray(value)
    )


def to_host(x) -> np.ndarray:
    """Fetch a replicated result on this process (np.asarray would demand
    every shard be addressable, which is false multi-process)."""
    return np.asarray(x.addressable_data(0))


def sharded_topk_global(
    queries: np.ndarray,  # [B, D] f32 — identical on every process
    corpus_local: np.ndarray,  # [n_local, D] this process's corpus rows
    valid_local: np.ndarray,  # [n_local] bool
    k: int,
    *,
    mesh: Any = None,
    axis: str = "data",
    metric: str = "cosine",
    bf16: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Multi-host KNN (BASELINE: 1M docs sharded across a pod): each
    process contributes its corpus shard; queries are replicated; local
    top-k results merge through an all-gather over ICI/DCN
    (ops/knn.sharded_topk's TPU-KNN recipe on a global mesh). Every shard
    must hold the same row count (pad + mask). Returns host (scores, ids)
    with ids indexing the GLOBAL corpus (process-major order)."""
    import jax

    from pathway_tpu.ops.knn import _sharded_topk_impl

    if mesh is None:
        mesh = global_mesh(axis)
    n_shards = mesh.shape[axis]
    n_local = corpus_local.shape[0]
    assert n_local % max(1, (n_shards // jax.process_count())) == 0
    corpus = from_process_local(corpus_local.astype(np.float32), mesh, axis)
    valid = from_process_local(np.asarray(valid_local, bool), mesh, axis)
    n_global = corpus.shape[0]
    from pathway_tpu.ops.knn import shard_base_indices

    base = shard_base_indices(n_global, n_shards)
    local_rows = n_global // jax.process_count()
    start = jax.process_index() * local_rows
    base_idx = from_process_local(
        base[start : start + local_rows], mesh, axis
    )
    q = replicated(np.asarray(queries, np.float32), mesh)
    sc, ix = _sharded_topk_impl(
        q, corpus, valid, base_idx, k, metric, bf16, mesh, axis
    )
    return to_host(sc), to_host(ix)
