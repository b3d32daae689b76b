"""Seeded weights of a hybrid state-space trunk configuration, made by the
benchmark.

As ``weights_trunk.py`` (whose leaf-by-leaf draw on the device this file
uses): the program says only what *shape* its parameter tree has, every value
is drawn here from ``--seed``, and one tree goes to the program and to the
plain reference alike. A leaf this file has no rule for raises.

The rules (``N`` a standard gaussian of the leaf's shape, drawn in float32,
stored in the dtype the program's tree states):

* every norm's gain (the Mamba mixer's gated norm too): ``1 + 0.1 N``; a
  kernel ``[in, ...]`` (``w_in``, ``w_out``, key and value projections, the
  shared expert, the router): ``N / sqrt(in)``; the attention's output
  ``[heads, e, d]``: ``N / sqrt(heads e)``; the experts' kernels ``[experts,
  in, out]``: ``N / sqrt(in)``;
* the embedding: ``N / embedding_multiplier``, so that ``x_0`` enters at unit
  scale, which is what the multiplier is there to do. With ``N`` alone the
  stream would start at 12 and every branch (0.22 times something of order
  1) would change the pooled vector by less than the stream's own bfloat16
  rounding: nothing a layer does would be visible;
* the query projection ``[d, heads, e]``: ``QUERY_SCALE sqrt(e) N / sqrt(d)``:
  under ``attention_multiplier`` = 1 / e a row's logits then have a standard
  deviation of ``QUERY_SCALE`` = 4 and its softmax rests on a few keys, as a
  trained model's does (``weights_gqa.py`` says what a flat softmax hides);
* Mamba-2's published initialisation (arXiv:2405.21060 and its reference
  code) for what the recurrence turns on: ``A_log = log U(1, 16)``,
  ``dt_bias`` the inverse softplus of a log-uniform 1e-3..1e-1 (so a head
  forgets over about ``1 / (dt A)``: from under a token to a thousand, and a
  share of the heads remembers across several 256-token chunks), ``D`` = 1;
  the convolution's taps ``[4, channels]``: ``N / sqrt(4)``; its bias
  ``0.1 N`` (zero would leave the bias untested).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key
from benchmarks.harness.weights_trunk import _leaf

QUERY_SCALE = 4.0

GAINS = {"attn_norm", "ffn_norm", "final_norm", "norm"}
KERNELS = {"w_in", "w_out", "wk", "wv", "w_gate", "w_up", "w_down", "router"}
OWN = {"conv": "conv", "conv_bias": "conv_bias", "A_log": "a_log", "dt_bias": "dt_bias", "D": "ones"}


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
    if name in OWN:
        return OWN[name]
    if name in KERNELS:
        return "expert_kernel" if parent == "ffn" and len(shape) == 3 else "kernel"
    raise ValueError(f"weights_ssm has no rule for the leaf {path!r} of shape {shape}")


@functools.partial(jax.jit, static_argnames=("rule", "shape", "dtype"))
def _own_leaf(key, rule: str, shape: tuple, dtype):
    if rule == "conv":
        value = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    elif rule == "conv_bias":
        value = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif rule == "a_log":
        value = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif rule == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        value = dt + jnp.log(-jnp.expm1(-dt))  # softplus(value) = dt
    elif rule == "ones":
        value = jnp.ones(shape, jnp.float32)
    else:
        raise ValueError(f"unknown rule {rule!r}")
    return value.astype(dtype)


def make_params(template, seed: int, embedding_multiplier: float = 1.0):
    """A tree shaped like ``template`` (arrays or ShapeDtypeStructs), each
    leaf drawn by its rule from its own fold of the seed's key."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    key = seed_key(seed)
    made = []
    for i, (path, leaf) in enumerate(leaves):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        shape, dtype = tuple(leaf.shape), jnp.dtype(leaf.dtype)
        rule, fold = rule_of(name, shape), jax.random.fold_in(key, i)
        if rule in OWN.values():
            made.append(_own_leaf(fold, rule, shape, dtype))
        elif rule in ("query", "embedding"):
            scale = QUERY_SCALE * math.sqrt(shape[-1]) if rule == "query" else 1.0 / embedding_multiplier
            drawn = _leaf(fold, "kernel" if rule == "query" else rule, shape, jnp.dtype(jnp.float32))
            made.append((scale * drawn).astype(dtype))
        else:
            made.append(_leaf(fold, rule, shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, made)
