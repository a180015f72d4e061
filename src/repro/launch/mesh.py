"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax import and
then calls it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the models place arrays
    with sharding constraints and let XLA propagate, which ``Explicit`` axes
    (the installed JAX's default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 single pod (16x16 data x model) or 2 pods (2 x 16 x 16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU smoke runs (requires enough host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_sharded_pack_mesh(n_shards: int, n_data: int = 1):
    """Debug mesh whose 'model' axis width matches a ShardedPack's shard count.

    ``ApproxConfig(mode="sharded_pack", pack_shards=N)`` distributes only when
    the bound mesh's 'model' axis is exactly N wide (see
    ``approx.table_pack._active_pack_mesh``); this helper builds that mesh for
    CPU smoke runs (``XLA_FLAGS=--xla_force_host_platform_device_count=...``
    must provide n_data * n_shards host devices before the first jax import).
    """
    return _auto_mesh((n_data, n_shards), ("data", "model"))
