"""Seeded weights of a grouped-query trunk configuration, made by the benchmark.

As ``weights_trunk.py`` (whose leaf-by-leaf draw on the device this file
uses): the program says only what *shape* its parameter tree has, every value
is drawn here from ``--seed``, and one tree goes to the program and to the
plain reference alike. A leaf this file has no rule for raises.

The rules (``N`` a standard gaussian of the leaf's shape, drawn in float32,
stored in the dtype the program's tree states):

* the embedding: ``N``; every norm's gain: ``1 + 0.1 N``;
* the key and value projections ``[d, heads, e]``: ``N / sqrt(d)``; the
  attention's output ``[heads, e, d]``: ``N / sqrt(heads e)``; the experts'
  kernels ``[experts, in, out]`` and the shared experts' ``[in, out]``:
  ``N / sqrt(in)``; the router ``[d, experts]``: ``N / sqrt(d)``;
* the query projection: ``QUERY_SCALE N / sqrt(d)``.

``QUERY_SCALE`` is 4: a row's logits then have a standard deviation of 4 and
its softmax rests on a few keys, as a trained model's does. At 1 the softmax
over 4,096 keys is nearly flat, its output averages the values away, the
attention adds a few percent to the residual, and a wrong mask (a window
layer that sees the whole row) would move a vector by less than bfloat16
rounding does: ``correct`` could not see it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key
from benchmarks.harness.weights_trunk import _leaf

QUERY_SCALE = 4.0

GAINS = {"norm", "final_norm"}
KERNELS = {"wk", "wv", "w_gate", "w_up", "w_down", "router"}


def rule_of(path: str, shape: tuple) -> str:
    """The rule a leaf is drawn by, from where it sits in the tree."""
    parts = path.split("/")
    name, parent = parts[-1], parts[-2] if len(parts) > 1 else ""
    if name == "embed":
        return "embedding"
    if name in GAINS:
        return "gain"
    if name == "wq":
        return "query"
    if name == "wo":
        return "kernel_out"
    if name in KERNELS:
        return "expert_kernel" if parent == "ffn" and len(shape) == 3 else "kernel"
    raise ValueError(f"weights_gqa has no rule for the leaf {path!r} of shape {shape}")


def make_params(template, seed: int):
    """A tree shaped like ``template`` (arrays or ShapeDtypeStructs), each
    leaf drawn by its rule from its own fold of the seed's key."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    key = seed_key(seed)
    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        shape, dtype = tuple(leaf.shape), jnp.dtype(leaf.dtype)
        rule = rule_of(name, shape)
        if rule == "query":
            drawn = _leaf(jax.random.fold_in(key, i), "kernel", shape, jnp.dtype(jnp.float32))
            made.append((QUERY_SCALE * drawn).astype(dtype))
        else:
            made.append(_leaf(jax.random.fold_in(key, i), rule, shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, made)
