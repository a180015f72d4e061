"""Logical-axis sharding: models annotate activations with *logical* axis names;
the launcher binds them to physical mesh axes.  Without an active binding the
annotations are no-ops, so smoke tests run un-meshed.

    with use_sharding(mesh, LOGICAL_RULES):
        loss = jax.jit(train_step, ...)(...)

Rules map logical names -> mesh axis (or tuple of axes, or None).  The defaults
implement the DESIGN.md §6 layout: batch over ('pod','data'), feature/expert/vocab
/head dims over 'model', sequence unsharded.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


def default_rules(mesh: Mesh) -> Dict[str, Any]:
    axes = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in axes) or None
    model = "model" if "model" in axes else None
    # experts also shard over the pod axis on multi-pod meshes (EP=32): halves
    # the per-chip expert work copy — what makes qwen3-235B fit 2 pods
    expert = (("pod", "model") if ("pod" in axes and model) else model)
    return {
        "batch": batch,
        "model": model,
        "expert": expert,
        "vocab": model,
        "heads": model,
        "ff": model,
    }


@contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[Dict[str, Any]] = None):
    prev = getattr(_ctx, "binding", None)
    _ctx.binding = (mesh, rules or (default_rules(mesh) if mesh else {}))
    try:
        yield
    finally:
        _ctx.binding = prev


def current_mesh() -> Optional[Mesh]:
    b = getattr(_ctx, "binding", None)
    return b[0] if b else None


def logical_to_spec(*logical) -> P:
    b = getattr(_ctx, "binding", None)
    rules = b[1] if b else {}
    return P(*(rules.get(l) if l is not None else None for l in logical))


def map_elementwise(f, x: jax.Array):
    """``f(x)`` for an elementwise ``f``, run per shard under the active mesh.

    XLA cannot partition a Pallas (Mosaic) kernel, so under a binding the
    call is wrapped in ``shard_map``: the last dim splits over 'model' and the
    first over the batch axes where they divide, any other dim stays whole.
    Being elementwise, ``f`` gives the same values on any split.  Without a
    binding this is ``f(x)``.  ``f`` may return a tuple of x-shaped arrays.

    The split is guessed from the shape, not read from the activation's
    logical axes.  It matches (batch, ..., ff|vocab|heads) activations — the
    MLP/GLU hidden, softcapped logits, Mamba2's dt; elsewhere (MoE expert
    blocks, xLSTM's (B, H, S) gates) XLA reshards around the call.
    """
    b = getattr(_ctx, "binding", None)
    if not b or b[0] is None:
        return f(x)
    mesh, rules = b
    sizes = dict(mesh.shape)
    width = lambda ax: int(np.prod([sizes[a] for a in
                                    (ax if isinstance(ax, tuple) else (ax,))]))
    spec = [None] * x.ndim
    model, batch = rules.get("model"), rules.get("batch")
    if x.ndim and model is not None and x.shape[-1] % width(model) == 0:
        spec[-1] = model
    if x.ndim > 1 and batch is not None and x.shape[0] % width(batch) == 0:
        spec[0] = batch
    spec = P(*spec)
    # pallas_call has no shard_map varying-axes rule: skip that check
    return jax.shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def shard_activation(x: jax.Array, *logical) -> jax.Array:
    """with_sharding_constraint under the active binding; identity otherwise."""
    b = getattr(_ctx, "binding", None)
    if not b or b[0] is None:
        return x
    mesh, rules = b
    spec = P(*(rules.get(l) if l is not None else None for l in logical))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------- ShardedTablePack operand rules -------------------------------
#
# The sharded pack stacks one values slice (and one local_base/owned plane
# pair) per shard on a leading axis that lays over the mesh 'model' axis;
# the selector metadata is replicated.  These specs are what makes the VMEM
# story real: device_put with them and each core holds ONE slice, not S.


def sharded_pack_pspecs(mesh: Mesh):
    """PartitionSpecs for every :class:`repro.approx.ShardedTablePack` leaf.

    The leading (shard) axis of ``local_base`` / ``owned`` / ``values`` maps
    to 'model'; ``boundaries`` / ``inv_delta`` / ``seg_count`` replicate.
    Returns a dict keyed by field name (static fields carry no spec).
    """
    model = "model" if "model" in mesh.axis_names else None
    return {
        "boundaries": P(None, None),
        "inv_delta": P(None, None),
        "seg_count": P(None, None),
        "local_base": P(model, None, None),
        "owned": P(model, None, None),
        "values": P(model, None),
    }


def place_sharded_pack(pack, mesh: Mesh):
    """device_put a ShardedTablePack so each 'model' shard holds one slice.

    Requires ``mesh.shape['model'] == pack.n_shards``.  The returned pack is
    what the shard_map lookup path (``eval_sharded_mesh``) consumes without
    any resharding transfer.
    """
    if "model" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
    if mesh.shape["model"] != pack.n_shards:
        raise ValueError(
            f"mesh 'model' axis is {mesh.shape['model']} wide but the pack "
            f"has {pack.n_shards} shards")
    specs = sharded_pack_pspecs(mesh)
    kw = {
        name: (jax.device_put(getattr(pack, name),
                              NamedSharding(mesh, specs[name]))
               if name in specs else getattr(pack, name))
        for name in pack._fields
    }
    return type(pack)(**kw)
