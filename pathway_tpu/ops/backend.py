"""What the backend jax initialized can and cannot do, decided once.

Mosaic compiles Pallas kernels for the TPU and for nothing else, so on
every other backend a Pallas kernel runs in Pallas interpret mode (how
the tests cover the kernels on the CPU).  That choice is made HERE, from
the backend jax initialized, and nowhere else: the kernels resolve
``interpret=None`` through :func:`pallas_interpret`, the decode scheduler
picks its attention kernel from it, and ``pathway_build_info`` shows it
(label ``pallas``).  A compiled kernel the compiler refuses raises; no
caller routes a refused kernel to a reference implementation.
"""

from __future__ import annotations


def pallas_interpret() -> bool:
    """True when Pallas kernels run interpreted (any non-TPU backend)."""
    import jax

    return jax.default_backend() != "tpu"


def pallas_mode() -> str:
    """``"compiled"`` on a TPU, ``"interpret"`` elsewhere (metric label)."""
    return "interpret" if pallas_interpret() else "compiled"


def float64_native() -> bool:
    """False on a TPU, which has no float64 unit: with x64 enabled XLA
    emulates it in pairs of float32.  Measured on a v5e (PR 21): ~48
    mantissa bits, float32's exponent range (1e39 -> inf, 1e-300 -> 0,
    3e38 * 2 -> nan), and a float64 -> int64 cast that rounds instead of
    truncating (123456789.12 -> 123456790).  int64 is emulated exactly.
    Programs that must equal numpy's float64 consult this and refuse."""
    import jax

    return jax.default_backend() != "tpu"
