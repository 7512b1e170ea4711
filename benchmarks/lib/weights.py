"""Weights from `--seed`, made by the benchmark and given to the program.

One rule fills every leaf, keyed by the leaf's path string, so the
plain references regenerate the same values from the seed alone and
take nothing the program made. jax's threefry is partitionable, so a
leaf has the same values whether it is generated whole or sharded.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

#: std of kernels and embeddings (both families publish 0.02)
STD = 0.02


def base_key(seed: int):
    """`--seed` may exceed 31 bits; fold the high part in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def make_leaf(key, path: str, shape, dtype):
    """Norm scales sit around one, biases and matrices around zero; no
    leaf is all zeros, so a term left out of the mathematics shows."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(k, shape, jnp.float32)
    if path.endswith("scale"):
        leaf = 1.0 + 0.1 * noise
    else:
        leaf = STD * noise
    return leaf.astype(dtype)


def fill(seed_key, shapes: dict) -> dict:
    """`shapes`: {path: (shape, dtype)} -> {path: array}."""
    return {p: make_leaf(seed_key, p, s, d) for p, (s, d) in shapes.items()}


def fill_like(seed_key, tree):
    """A pytree of `jax.ShapeDtypeStruct` (the program's own parameter
    tree, from `jax.eval_shape`) filled leaf by leaf from the seed."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: make_leaf(seed_key, path_str(path), leaf.shape,
                                     leaf.dtype), tree)


def flat(tree) -> dict:
    """{path string: leaf} of a pytree."""
    return {path_str(p): leaf for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
