"""Seeded weights of a window-with-sinks configuration, made by the benchmark.

As ``weights_trunk.py`` (whose leaf-by-leaf draw on the device this file
uses): the program says only what *shape* its parameter tree has, every value
is drawn here from ``--seed``, and one tree goes to the program and to the
plain reference alike. A leaf this file has no rule for raises.

The rules (``N`` a standard gaussian of the leaf's shape, drawn in float32,
stored in the dtype the program's tree states):

* the embedding: ``N``; every norm's gain: ``1 + 0.1 N``;
* the key and value projections ``[d, heads, e]``, the dense FFN's and the
  router ``[d, experts]``: ``N / sqrt(d)``; the attention's output ``[heads,
  e, d]``: ``N / sqrt(heads e)``; the experts' kernels ``[experts, in,
  out]``: ``N / sqrt(in)``; the router's correction bias: ``0.01 N``, as
  xing4's (``weights_trunk.ROUTER_BIAS_SIGMA``);
* **adjusted**, so that what a layer does is visible:

  - the query projection: ``QUERY_SCALE N / sqrt(d)``, so a row's logits
    (192 dims at ``192^-1/2``) have a standard deviation of about 4 and its
    softmax rests on a few keys, as a trained model's does
    (``weights_gqa.py``): a flat softmax over 128 keys averages the values
    away, and a wrong window would move a vector by less than rounding;
  - each query head's sink uniform in ``SINK_RANGE``: over 128 keys with
    logits of standard deviation 4 a row's log-sum-exp is about 10.9 (9.6 to
    12.8 between the tenth and ninetieth percentiles), so a sink of 8.7 to
    10.9 holds between a tenth and a half of a typical window row's mass. At
    the 0 a sink starts from in training it would hold 2e-5 of it, and a
    kernel that dropped the sink would compute the same vectors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key
from benchmarks.harness.weights_gqa import QUERY_SCALE
from benchmarks.harness.weights_trunk import _leaf

SINK_RANGE = (8.7, 10.9)

GAINS = {"attn_norm", "ffn_norm", "final_norm"}
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
    if name == "sinks":
        return "sink"
    if name == "bias" and parent == "ffn":
        return "router_bias"
    if name in KERNELS:
        return "expert_kernel" if parent == "ffn" and len(shape) == 3 else "kernel"
    raise ValueError(f"weights_swa has no rule for the leaf {path!r} of shape {shape}")


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
        if rule == "query":
            drawn = _leaf(fold, "kernel", shape, jnp.dtype(jnp.float32))
            made.append((QUERY_SCALE * drawn).astype(dtype))
        elif rule == "sink":
            made.append(jax.random.uniform(fold, shape, jnp.float32, *SINK_RANGE).astype(dtype))
        else:
            made.append(_leaf(fold, rule, shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, made)
