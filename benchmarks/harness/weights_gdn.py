"""Seeded weights of a gated-delta-rule configuration, made by the benchmark.

As ``weights_trunk.py`` (whose leaf-by-leaf draw on the device this file
uses): the program says only what *shape* its parameter tree has, every value
is drawn here from ``--seed``, and one tree goes to the program and to the
plain reference alike. A leaf this file has no rule for raises.

The rules (``N`` a standard gaussian of the leaf's shape, drawn in float32,
stored in the dtype the program's tree states):

* the embedding: ``N``; a zero-centred norm's ``w`` (every layer norm and the
  final norm, whose gain is ``1 + w``): ``0.1 N``; the delta rule's gated
  norm, a plain gain: ``1 + 0.1 N``;
* a kernel ``[in, ...]`` (``w_qkvz``, ``w_ba``, ``w_out``, the attention's
  projections, the router, the shared expert and its gate): ``N / sqrt(in)``;
  the attention's output ``[heads, e, d]``: ``N / sqrt(heads e)``; the
  experts' kernels ``[experts, in, out]``: ``N / sqrt(in)``; the
  convolution's taps ``[4, channels]``: ``N / sqrt(4)``;
* the family's initialisation for ``dt_bias``: 1;
* **adjusted**, so that what a layer does is visible:

  - ``A_log = log A``, ``A`` log-uniform in ``A_RANGE`` (1e-3..4e-2) where
    the family draws ``A`` uniform in 0..16: with ``softplus(a + 1)`` about
    0.3..3, ``exp(g)`` then lies mostly between 0.9 and 0.999, so a head
    remembers over 20 to 800 positions, across several 64-position chunks,
    and a scan that loses its carried state, or drops its delta term, moves
    the vectors. With ``A`` up to 16 most heads would forget within a token
    and neither fault could be seen;
  - the attention's q and k norms' ``w``: ``1 + 0.1 N``, so ``1 + w`` is
    about 2 and a row's logits (unit-RMS queries and keys of 256 at
    ``256^-1/2``) have a standard deviation of about 4: the softmax rests on a
    few keys, as a trained model's does (``weights_gqa.py`` says what a flat
    softmax hides). The q/k norm takes out every scale of ``W_q`` and
    ``W_k``, so this is the only place to set it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key
from benchmarks.harness.weights_trunk import _leaf

A_RANGE = (1e-3, 4e-2)

OFFSET_GAINS = {"attn_norm", "ffn_norm", "final_norm"}
GAINS = {"norm", "q_norm", "k_norm"}
KERNELS = {"w_qkvz", "w_ba", "w_out", "wq", "wk", "wv", "w_gate", "w_up", "w_down", "router", "shared_gate"}
OWN = {"conv": "conv", "A_log": "a_log", "dt_bias": "ones"}


def rule_of(path: str, shape: tuple) -> str:
    """The rule a leaf is drawn by, from where it sits in the tree."""
    parts = path.split("/")
    name, parent = parts[-1], parts[-2] if len(parts) > 1 else ""
    if name == "embed":
        return "embedding"
    if name in OFFSET_GAINS:
        return "offset_gain"
    if name in GAINS:
        return "gain"
    if name == "wo":
        return "kernel_out"
    if name in OWN:
        return OWN[name]
    if name in KERNELS:
        return "expert_kernel" if parent == "ffn" and len(shape) == 3 else "kernel"
    raise ValueError(f"weights_gdn has no rule for the leaf {path!r} of shape {shape}")


@functools.partial(jax.jit, static_argnames=("rule", "shape", "dtype"))
def _own_leaf(key, rule: str, shape: tuple, dtype):
    if rule == "conv":
        value = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    elif rule == "a_log":
        value = jax.random.uniform(key, shape, jnp.float32, math.log(A_RANGE[0]), math.log(A_RANGE[1]))
    elif rule == "ones":
        value = jnp.ones(shape, jnp.float32)
    elif rule == "offset_gain":
        value = 0.1 * jax.random.normal(key, shape, jnp.float32)
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
        shape, dtype = tuple(leaf.shape), jnp.dtype(leaf.dtype)
        rule, fold = rule_of(name, shape), jax.random.fold_in(key, i)
        if rule in ("conv", "a_log", "ones", "offset_gain"):
            made.append(_own_leaf(fold, rule, shape, dtype))
        else:
            made.append(_leaf(fold, rule, shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, made)
