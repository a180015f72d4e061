"""Where JAX's persistent compilation cache lives, for every entry point.

Call :func:`use_compile_cache` before the first compile.  The cache path is
part of what makes a later run hit, so it is fixed: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX's own config reads that variable, so nothing
is set here), otherwise ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
