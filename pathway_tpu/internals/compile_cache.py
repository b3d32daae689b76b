"""Where this process keeps XLA's persistent compile cache.

Every process entry that will own a device calls
:func:`configure_compile_cache` once before it compiles (``pw.run``,
``serving/replica.py main``, ``chip_smoke.py``, ``benchmarks/``).  The
directory is placed from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set jax reads it by itself and nothing
is set in code; otherwise the cache lives at one fixed path inside the
checkout, ``<checkout>/.jax_cache`` (git-ignored).  The path is part of
the cache key, so it is never a temp dir, a pid or a timestamp.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the directory that holds the
    ``pathway_tpu`` package, plus the fixed name."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def configure_compile_cache() -> str:
    """Point jax at the compile cache and return the directory in use.
    Idempotent; does not initialize a backend."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
