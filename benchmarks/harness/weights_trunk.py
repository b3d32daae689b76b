"""Seeded weights of a trunk configuration, made by the benchmark.

The program says only what *shape* its parameter tree has (a tree of
``ShapeDtypeStruct``, ``sut_trunk.seed_weights``); every value is drawn here,
from ``--seed``, by the rules below, leaf by leaf on the device (4.3 B
parameters have no host copy, and a leaf's float32 draw is gone before the
next is made). One tree goes to the program and the same arrays to the plain
reference, so what the cell computes, how evenly its router spreads the
tokens and how much the residual's coefficients depend on their input are
this file's to say, not the program's. A leaf this file has no rule for
raises: a new kind of parameter gets its rule here first.

The rules (``N`` a standard gaussian of the leaf's shape, drawn in float32,
stored in the dtype the program's tree states):

* the embedding: ``N``; every norm's gain: ``1 + 0.1 N``;
* a kernel ``[in, ...]``: ``N / sqrt(in)``; the attention's output
  ``[heads, d_v, out]``: ``N / sqrt(heads d_v)``; the experts' kernels
  ``[experts, in, out]``: ``N / sqrt(in)``; the router ``[d, experts]``:
  ``N / sqrt(d)``;
* the router's correction bias: ``ROUTER_BIAS_SIGMA N`` (the correction is
  exercised: it changes some tokens' choice);
* the residual's coefficient projections ``[n, d, n + n + n n]``:
  ``N / sqrt(n d)``, so that the projected, normalised streams are of order 1;
  its scalars a_pre, a_post, a_res: ``MHC_ALPHA``; its biases: b_pre at
  logit(1/n), b_post at 0, b_res at ``MHC_RES_DIAGONAL`` I (H_res starts near
  the identity, diagonal about 0.7), plus ``0.1 N``, ``0.1 N``, ``0.3 N`` so
  that no coefficient is symmetric by accident.

``MHC_ALPHA`` is 0.5, not the 0.01 at which arXiv:2512.24880 *initialises*
training: at 0.01 the input-dependent part of the coefficients is about 0.01
and a fault in the projection would move a vector by less than bfloat16
rounding does, so ``correct`` could not see it; at 0.5 H_pre swings between
about 0.17 and 0.35 from token to token.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key

ROUTER_BIAS_SIGMA = 0.01
MHC_ALPHA = 0.5
MHC_RES_DIAGONAL = 2.0

GAINS = {"attn_norm", "ffn_norm", "q_norm", "kv_norm", "final_norm", "norm"}
KERNELS = {"wq_a", "wq_b", "wkv_a", "wkv_b", "w_gate", "w_up", "w_down", "router"}
RESIDUALS = {"attn_res", "ffn_res"}


def rule_of(path: str, shape: tuple) -> str:
    """The rule a leaf is drawn by, from where it sits in the tree."""
    parts = path.split("/")
    name, parent = parts[-1], parts[-2] if len(parts) > 1 else ""
    if parent in RESIDUALS and name in ("proj", "alpha", "bias"):
        return "mhc_" + name
    if name == "embed":
        return "embedding"
    if name in GAINS:
        return "gain"
    if name == "bias" and parent == "ffn":
        return "router_bias"
    if name == "wo":
        return "kernel_out"
    if name in KERNELS:
        return "expert_kernel" if parent == "ffn" and len(shape) == 3 else "kernel"
    raise ValueError(f"weights_trunk has no rule for the leaf {path!r} of shape {shape}")


@functools.partial(jax.jit, static_argnames=("rule", "shape", "dtype"))
def _leaf(key, rule: str, shape: tuple, dtype):
    normal = jax.random.normal(key, shape, jnp.float32)
    if rule == "embedding":
        value = normal
    elif rule == "gain":
        value = 1.0 + 0.1 * normal
    elif rule == "kernel":
        value = normal / math.sqrt(shape[0])
    elif rule == "expert_kernel":
        value = normal / math.sqrt(shape[1])
    elif rule in ("kernel_out", "mhc_proj"):
        value = normal / math.sqrt(shape[0] * shape[1])
    elif rule == "router_bias":
        value = ROUTER_BIAS_SIGMA * normal
    elif rule == "mhc_alpha":
        value = jnp.full(shape, MHC_ALPHA, jnp.float32)
    elif rule == "mhc_bias":
        n = math.isqrt(1 + shape[0]) - 1  # n + n + n n entries
        centre = jnp.concatenate(
            [
                jnp.full((n,), -math.log(n - 1.0) if n > 1 else 0.0),
                jnp.zeros((n,)),
                MHC_RES_DIAGONAL * jnp.eye(n).reshape(-1),
            ]
        )
        spread = jnp.concatenate([jnp.full((2 * n,), 0.1), jnp.full((n * n,), 0.3)])
        value = centre + spread * normal
    else:
        raise ValueError(f"unknown rule {rule!r}")
    return value.astype(dtype)


def make_params(template, seed: int):
    """A tree shaped like ``template`` (arrays or ShapeDtypeStructs), each
    leaf drawn by its rule from its own fold of the seed's key."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    key = seed_key(seed)
    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        shape = tuple(leaf.shape)
        made.append(
            _leaf(jax.random.fold_in(key, i), rule_of(name, shape), shape, jnp.dtype(leaf.dtype))
        )
    return jax.tree_util.tree_unflatten(treedef, made)
