"""Seeded weights, made on the device in one jitted call, float32 as served.

The benchmark makes the weights, hands one copy to the program (in place of
its own fixed-seed initialisation) and the same arrays to the plain reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one past 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":  # layer norm gain
        return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
    if name == "bias":
        return 0.05 * jax.random.normal(key, shape, dtype)
    if name == "embedding":
        return jax.random.normal(key, shape, dtype)
    # a kernel: [in, ...out] or, for the attention output, [heads, hd, out]
    fan_in = shape[0] * shape[1] if path.endswith("out/kernel") else shape[0]
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(float(fan_in))


def make_params(template, seed: int):
    """A tree shaped like ``template`` (arrays or ShapeDtypeStructs)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    paths = [
        "/".join(str(getattr(k, "key", k)) for k in path) for path, _ in leaves
    ]
    shapes = [(leaf.shape, leaf.dtype) for _, leaf in leaves]

    @jax.jit
    def build(key):
        return [
            _leaf(jax.random.fold_in(key, i), paths[i], shape, dtype)
            for i, (shape, dtype) in enumerate(shapes)
        ]

    return jax.tree_util.tree_unflatten(treedef, build(seed_key(seed)))
