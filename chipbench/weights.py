"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program, so that the reference can
make the same ones again after the program's state is freed.  The tree has the
layout of the program's parameters (its leaf names and shapes, which the
caller passes as ``shapes``); the values are the benchmark's own:
N(0, 1)/sqrt(fan_in) for projections, N(0, 0.02) for the embeddings and the
attention output projection, and 1 for norm gains.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from traffic import seed_words


def key(seed: int) -> jax.Array:
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.key(lo), hi)


def _scale(path: tuple, shape: tuple, stacked: bool):
    names = [getattr(p, "key", str(p)) for p in path]
    if names[-1] == "g":
        return None  # norm gain
    if "table" in names or "wo" in names:
        return 0.02
    fan_in = shape[1] if stacked else shape[0]
    return fan_in ** -0.5


def make(shapes, seed: int):
    """The weights for ``shapes`` (a tree of ShapeDtypeStruct) from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        out = []
        for i, (path, s) in enumerate(leaves):
            stacked = getattr(path[0], "key", None) == "layers"
            scale = _scale(path, s.shape, stacked)
            if scale is None:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                out.append(scale * jax.random.normal(
                    jax.random.fold_in(k, i), s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key(seed))
