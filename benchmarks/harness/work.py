"""Operations and bytes of each call, from shapes alone.

The counts are what the algorithm needs at the precision the configuration
states (bf16 rows and multiplies), the same whatever implements the call, so
that a later kernel or dtype change cannot make a share read over 100%.
"""

from __future__ import annotations


def topk_flops(batch: int, rows: int, dim: int) -> float:
    """Score matmul of ``batch`` queries against ``rows`` corpus rows."""
    return 2.0 * batch * rows * dim


def topk_bytes(batch: int, rows: int, dim: int, k: int) -> float:
    """One scan of the corpus at bf16, its validity mask, the float32
    queries in, and (score, id) pairs out."""
    return rows * dim * 2.0 + rows + batch * dim * 4.0 + batch * k * 8.0


def encoder_flops(tokens: int, dim: int, depth: int) -> float:
    """Forward pass of one sequence of ``tokens`` tokens through ``depth``
    blocks of width ``dim`` with a 4x feed-forward: 24*t*dim^2 for the six
    projections' multiply-adds and 4*t^2*dim for scores and mixing."""
    return depth * (24.0 * tokens * dim * dim + 4.0 * tokens * tokens * dim)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least seconds the chip could take, and which bound applied."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "memory"
