"""Weights made by the benchmark from the seed, on the device, in one
jitted call, in the dtype they are served in.

The program's own ``init_params`` gives only the layout (an ``eval_shape``,
nothing computed). Every leaf is drawn here, so the plain reference reads
weights that the program did not make. The values keep activations of a
plausible size at every depth: matrices are normal with std 1/sqrt(fan-in),
the embedding 0.02, norm scales 1 + N(0, 0.1), and every bias N(0, 0.02)
so that the QKV-bias and LayerNorm-bias paths carry numbers.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _draw(key, name: str, shape, dtype, layered: bool):
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if leaf.startswith("b") and len(shape) <= 1 + layered:
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name == "embed":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, dtype) *
            jnp.asarray(fan_in ** -0.5, dtype))


def make_params(layout, seed: int, sharding=None):
    """Draw a params pytree of ``layout`` (a pytree of ShapeDtypeStructs)
    from ``seed``. Leaf keys come from the seed and the leaf's path, so a
    leaf's values do not depend on which other leaves exist."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [s for _, s in flat]

    def build(root):
        leaves = []
        for name, s in zip(names, specs):
            k = jax.random.fold_in(root, zlib.crc32(name.encode()))
            layered = name.startswith("blocks/")
            leaves.append(_draw(k, name, s.shape, s.dtype, layered))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    root = seed_key(seed)
    fn = jax.jit(build, out_shardings=sharding)
    return fn(root)


def seed_key(seed: int):
    """A PRNG key for any whole seed, also one beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
