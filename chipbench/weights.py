"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program, so that the reference can
make the same ones again after the program's state is freed.  The tree has the
layout of the program's parameters (its leaf names and shapes, which the
caller passes as ``shapes``); the values are the benchmark's own: N(0, 1)
times the scale that the configuration's family (``families/<family>.py``)
gives each leaf, or 1 where it gives none (norm gains).  Leaf ``i`` of the
flattened tree draws from ``fold_in(key(seed), i)``, whatever the family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from traffic import seed_words


def key(seed: int) -> jax.Array:
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.key(lo), hi)


def make(shapes, seed: int, scale):
    """The weights for ``shapes`` (a tree of ShapeDtypeStruct) from ``seed``,
    each leaf scaled by ``scale(names, shape)`` (a family's ``scale``)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        out = []
        for i, (path, s) in enumerate(leaves):
            names = tuple(getattr(p, "key", str(p)) for p in path)
            sc = scale(names, s.shape)
            if sc is None:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                out.append(sc * jax.random.normal(
                    jax.random.fold_in(k, i), s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key(seed))
