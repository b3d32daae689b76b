"""Device mesh management.

TPU-native replacement for the reference's worker/process topology
(reference: src/engine/dataflow/config.rs:63-121 — PATHWAY_THREADS ×
PATHWAY_PROCESSES workers over TCP): scaling out means adding mesh devices,
not OS processes. The 'data' axis carries the key-shard dimension (the analog
of the reference's 16-bit key shards, src/engine/value.rs:38)."""

from __future__ import annotations

import os
from typing import Any, Sequence

import numpy as np


_default_mesh: Any = None


def make_mesh(
    n_devices: int | None = None,
    axis_names: Sequence[str] = ("data",),
    *,
    backend: str | None = None,
):
    """Build a Mesh over this process's devices of the default backend
    (or of ``backend``).  Asking for more devices than the backend has
    raises: a mesh is never silently built from another backend's
    devices.  Unit tests and the CPU dry run get their eight devices
    from ``--xla_force_host_platform_device_count`` on the CPU backend
    itself."""
    import jax
    from jax.sharding import Mesh

    if backend is not None:
        devices = jax.devices(backend)
    else:
        # LOCAL devices only: the engine mesh (host-driven per-tick
        # device_put/np.asarray round trips) must never include another
        # process's non-addressable devices; cross-process meshes are
        # built explicitly via parallel.distributed.global_mesh
        devices = jax.local_devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices, the "
                f"{devices[0].platform} backend has {len(devices)}"
            )
        devices = devices[:n_devices]
    shape = _factor_shape(len(devices), len(axis_names))
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, axis_names=tuple(axis_names))


def _factor_shape(n: int, n_axes: int) -> tuple[int, ...]:
    if n_axes == 1:
        return (n,)
    # put everything on the first axis by default; callers wanting tp×dp
    # meshes pass explicit shapes via Mesh directly
    return (n,) + (1,) * (n_axes - 1)


def set_default_mesh(mesh: Any) -> None:
    global _default_mesh
    _default_mesh = mesh


def get_mesh() -> Any:
    return _default_mesh


# --- engine mesh ------------------------------------------------------------
# When set, STATEFUL ENGINE OPERATORS themselves shard over the mesh (per-
# shard keyed state + all-to-all exchange, engine/sharded.py) — the analog of
# the reference's PATHWAY_THREADS worker count (config.rs:88-121). Activated
# explicitly via set_engine_mesh() or by the PATHWAY_ENGINE_SHARDS env var
# (which `pathway spawn -n N` sets instead of forking redundant processes).

_engine_mesh: Any = None
_engine_mesh_resolved = False


def set_engine_mesh(mesh: Any, axis: str = "data") -> None:
    """Enable (or disable with mesh=None) engine-level key sharding."""
    global _engine_mesh, _engine_mesh_resolved
    _engine_mesh = (mesh, axis) if mesh is not None else None
    _engine_mesh_resolved = True


def get_engine_mesh() -> tuple[Any, str] | None:
    global _engine_mesh, _engine_mesh_resolved
    if not _engine_mesh_resolved:
        _engine_mesh_resolved = True
        n = os.environ.get("PATHWAY_ENGINE_SHARDS", "")
        if n.isdigit() and int(n) > 1:
            # too few devices raises: sharding that was asked for is
            # never quietly dropped (on a CPU host the launcher widens
            # the device pool with xla_force_host_platform_device_count)
            _engine_mesh = (make_mesh(int(n)), "data")
    return _engine_mesh
