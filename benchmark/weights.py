"""Seeded weights made by the benchmark, on the device, in one jitted call.

The program and the plain reference both receive these arrays: the reference
takes nothing the program has made. The tree's structure (leaf names, shapes,
dtypes) comes from the program's own parameter tree; the values come from
``--seed`` here. Kernels are normal(0, 1/sqrt(fan_in)), embeddings normal(0, 0.02), LayerNorm
scales one, biases zero: logits come out with a spread near one, so greedy
tokens have margins to compare.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf(key, name: str, shape, dtype):
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    if name.endswith("bias"):
        return jnp.zeros(shape, dtype)
    if "embed" in name or len(shape) < 2:
        std = 0.02
    else:
        fan_in = 1
        for s in shape[:-1]:
            fan_in *= s
        std = fan_in ** -0.5
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make(template, seed: int):
    """A tree like ``template`` (arrays or ShapeDtypeStructs) filled from
    ``seed``. One jitted program; each leaf's key is folded from its path, so
    a tree with the same paths and shapes gets the same values whatever else
    it holds and in whatever order."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    keys = [[str(getattr(k, "key", getattr(k, "name", k))) for k in p]
            for p, _ in paths]
    shapes = [(tuple(x.shape), x.dtype) for _, x in paths]

    @jax.jit
    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, zlib.crc32("/".join(k).encode())),
                        k[-1], s, d) for k, (s, d) in zip(keys, shapes)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # Seeds run past 2**31; PRNGKey takes them modulo 2**32 either way.
    return build(jax.random.PRNGKey(int(seed) % (2 ** 32)))
