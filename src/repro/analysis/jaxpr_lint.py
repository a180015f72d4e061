"""Jaxpr-level lint primitives for PackLint (see ``repro.analysis.contracts``).

Everything here works on *traces* — ``jax.make_jaxpr`` / ``jax.eval_shape``
artifacts — and never executes a kernel.  The helpers are deliberately small
and composable: the contract rules in ``contracts.py`` decide *what* must
hold; this module only answers structural questions about a jaxpr:

- which primitives appear (recursively, through ``pjit``/``custom_jvp``/
  ``scan``/``pallas_call`` sub-jaxprs);
- which dtypes appear (avals, literals, and closed-over consts) — the
  f64-leakage lint;
- where the Pallas kernels are, what their kernel bodies contain, and what
  their grid/BlockSpec footprints are — the forbidden-primitive and static
  VMEM lints;
- what a ``jax.jit`` cache key looks like for a concrete call — the
  recompile-hazard lint (weak types and dtype drift show up here).
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import core as jax_core
from jax._src.core import ClosedJaxpr as _ClosedJaxpr
from jax._src.core import Jaxpr as _Jaxpr

# Dtypes that must never appear in a runtime trace: the design layer
# (core/design.py, core/quantize.py) works in f64 on purpose, and a single
# leaked f64 constant silently doubles VMEM traffic (or, with x64 disabled,
# silently *downcasts* the design guarantee).
WIDE_DTYPES = frozenset({"float64", "complex128"})


# --------------------------------------------------------------------------------------
# Recursive jaxpr walking
# --------------------------------------------------------------------------------------

def _as_jaxpr(obj) -> Optional[Any]:
    """Return the raw ``Jaxpr`` carried by ``obj`` (Jaxpr/ClosedJaxpr), else None."""
    if isinstance(obj, _ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, _Jaxpr):
        return obj
    if hasattr(obj, "jaxpr") and hasattr(obj, "eqns"):  # pragma: no cover
        return obj
    return None


def sub_jaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Yield every raw Jaxpr nested in an eqn's params (pjit's ``jaxpr``,
    pallas_call's kernel body, scan/cond branches, custom_jvp closures...)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            j = _as_jaxpr(item)
            if j is not None:
                yield j
            elif hasattr(item, "call_jaxpr"):  # custom_jvp_call wrappers
                j2 = _as_jaxpr(item.call_jaxpr)
                if j2 is not None:
                    yield j2


def iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations in ``jaxpr`` and every nested sub-jaxpr (depth-first)."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        return
    for eqn in j.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def primitive_counts(jaxpr) -> Counter:
    """``Counter`` of primitive names over the whole (recursive) trace."""
    return Counter(e.primitive.name for e in iter_eqns(jaxpr))


def iter_avals(jaxpr) -> Iterator[Tuple[str, Any]]:
    """All (where, aval) pairs in the trace: invars, constvars, every eqn's
    in/out vars (literals included), recursively."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        return
    for v in list(j.invars) + list(j.constvars):
        yield ("invar", v.aval)
    for eqn in j.eqns:
        name = eqn.primitive.name
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None and hasattr(v, "val"):  # Literal
                aval = jax.typeof(v.val)
            if aval is not None:
                yield (name, aval)
        for v in eqn.outvars:
            yield (name, v.aval)
        for sub in sub_jaxprs(eqn.params):
            yield from iter_avals(sub)


# --------------------------------------------------------------------------------------
# Rule 1 — wide-dtype (f64) leakage
# --------------------------------------------------------------------------------------

def find_wide_dtypes(traced, wide: frozenset = WIDE_DTYPES) -> List[str]:
    """Every place a forbidden-width dtype appears in the trace.

    Returns human-readable locations (``"mul: float64"``); empty list == clean.
    Consts of a ClosedJaxpr are checked too — that is where a design-layer
    ``np.float64`` table sneaks into a runtime closure.
    """
    hits: List[str] = []
    if isinstance(traced, _ClosedJaxpr):
        for i, c in enumerate(traced.consts):
            dt = getattr(c, "dtype", None)
            if dt is not None and str(dt) in wide:
                hits.append(f"const[{i}]: {dt}")
    for where, aval in iter_avals(traced):
        dt = getattr(aval, "dtype", None)
        if dt is not None and str(dt) in wide:
            hits.append(f"{where}: {dt}")
    return hits


def array_leaf_wide_dtypes(tree, wide: frozenset = WIDE_DTYPES) -> List[str]:
    """Wide-dtype leaves in a pytree of device/host arrays (a pack artifact)."""
    hits = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        dt = getattr(leaf, "dtype", None)
        if dt is not None and str(dt) in wide:
            hits.append(f"{jax.tree_util.keystr(path)}: {dt}")
    return hits


# --------------------------------------------------------------------------------------
# Rule 2 — Pallas kernel extraction, forbidden primitives, dynamic shapes
# --------------------------------------------------------------------------------------

def pallas_eqns(traced) -> List[Any]:
    """Every ``pallas_call`` equation in the trace (recursive)."""
    return [e for e in iter_eqns(traced) if e.primitive.name == "pallas_call"]


def kernel_name(eqn) -> str:
    """The kernel body's function name (the body jaxpr's debug info)."""
    return kernel_body(eqn).debug_info.func_name


def kernel_body(eqn):
    """The raw kernel-body Jaxpr of a ``pallas_call`` equation."""
    return _as_jaxpr(eqn.params["jaxpr"])


def kernel_primitive_counts(eqn) -> Counter:
    """Primitive census of one kernel body (recursing into nested pjit)."""
    return primitive_counts(kernel_body(eqn))


def forbidden_primitives(counts: Counter,
                         allowed: Optional[frozenset] = None) -> List[str]:
    """Primitives that must never appear in a device kernel body (or, with an
    ``allowed`` set, any primitive outside that per-entry allowlist)."""
    bad = []
    for name in sorted(counts):
        if "callback" in name or name in ("infeed", "outfeed"):
            bad.append(name)
        elif allowed is not None and name not in allowed:
            bad.append(f"unallowlisted:{name}")
    return bad


def closure_callbacks(traced) -> List[str]:
    """Host-callback primitives anywhere in a runtime closure's trace — the
    obs-off path must have none (rule 2's closure-level clause)."""
    return sorted(n for n in primitive_counts(traced)
                  if "callback" in n or n in ("infeed", "outfeed"))


def dynamic_shape_avals(jaxpr) -> List[str]:
    """Avals whose shape is not a tuple of concrete ints (dynamic dims)."""
    bad = []
    for where, aval in iter_avals(jaxpr):
        shape = getattr(aval, "shape", None)
        if shape is None:
            continue
        if not all(isinstance(d, (int, np.integer)) for d in shape):
            bad.append(f"{where}: {shape}")
    return bad


# --------------------------------------------------------------------------------------
# Rule 3 — jit cache keys (recompile hazards)
# --------------------------------------------------------------------------------------

def aval_of(x):
    """The shaped aval jax would assign ``x`` as a jit argument (weak types
    preserved — a python scalar comes back ``weak_type=True``)."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax_core.ShapedArray(x.shape, x.dtype,
                                    weak_type=getattr(x, "weak_type", False))
    return jax.typeof(x)


def leaf_signature(x) -> Tuple[Tuple[int, ...], str, bool]:
    """(shape, dtype, weak_type) — the per-leaf component of a jit cache key."""
    a = aval_of(x)
    return (tuple(a.shape), str(a.dtype), bool(getattr(a, "weak_type", False)))


def jit_cache_key(args: Sequence[Any],
                  static: Optional[Dict[str, Any]] = None) -> tuple:
    """The structural jit cache key of one call: (treedef, per-leaf
    (shape, dtype, weak_type), sorted static kwargs).

    Two calls that produce different keys WILL trigger a recompile of the
    underlying executable; the serving contracts require key equality across
    reroutes and ticks.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tuple(args))
    sig = tuple(leaf_signature(x) for x in leaves)
    stat = tuple(sorted((k, repr(v)) for k, v in (static or {}).items()))
    return (str(treedef), sig, stat)


def weak_leaves(args: Sequence[Any]) -> List[str]:
    """Indices/paths of weak-typed leaves in a call's dynamic args — each one
    is a promotion hazard (the next strongly-typed caller forces a recompile)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tuple(args))[0]:
        if leaf_signature(leaf)[2]:
            out.append(jax.tree_util.keystr(path))
    return out


def keys_stable(keys: Sequence[tuple]) -> bool:
    """True iff every captured call shares one cache key (no recompiles)."""
    return len({k for k in keys}) <= 1


# --------------------------------------------------------------------------------------
# Rule 4 — static VMEM accounting from lowered pallas_call footprints
# --------------------------------------------------------------------------------------

def _block_elems(block_shape) -> int:
    """Elements in one block: ``Blocked(block_size=k)`` dims count k,
    squeezed dims (no ``block_size``) count 1."""
    n = 1
    for d in block_shape:
        n *= int(getattr(d, "block_size", 1))
    return n


def pallas_footprint(eqn) -> Dict[str, Any]:
    """Static footprint of one lowered ``pallas_call``.

    Returns::

        {"grid": tuple, "operands": [(shape, dtype, full_bytes, block_bytes,
                                      pinned, smem)], "pinned_bytes": int,
         "block_bytes": int, "prefetch_bytes": int, "smem_bytes": int}

    ``operands`` follows ``grid_mapping.block_mappings`` order (inputs then
    outputs); scalar-prefetch operands (PrefetchScalarGridSpec) have no block
    mapping and are accounted separately under ``prefetch_bytes``.  An operand
    is *pinned* when its block covers the full array — the whole plane is
    VMEM-resident every grid step, which is exactly what the pack's
    ``vmem()`` budget prices.  Operands placed in SMEM (scalar-read selector
    metadata) are flagged ``smem``: they are whole-array and read-only, but
    live in scalar memory, so they count under ``smem_bytes`` and never as
    pinned VMEM.
    """
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    mappings = list(gm.block_mappings)
    # avals for [inputs..., outputs...]: invars after the scalar-prefetch
    # operands line up with the leading mappings; out_avals close the list.
    out_avals = list(eqn.params.get("out_avals") or [v.aval for v in eqn.outvars])
    in_avals = [v.aval if hasattr(v, "aval") else jax.typeof(v.val)
                for v in eqn.invars]
    n_prefetch = len(in_avals) + len(out_avals) - len(mappings)
    prefetch, block_ops = in_avals[:max(n_prefetch, 0)], in_avals[max(n_prefetch, 0):]
    avals = block_ops + out_avals

    operands = []
    pinned_bytes = block_bytes = smem_bytes = 0
    n_out = len(out_avals)
    for i, (aval, bm) in enumerate(zip(avals, mappings)):
        shape = tuple(int(d) for d in aval.shape)
        itemsize = np.dtype(aval.dtype).itemsize
        full = int(np.prod(shape, dtype=np.int64)) * itemsize if shape else itemsize
        bshape = tuple(bm.block_shape)
        blk = _block_elems(bshape) * itemsize
        smem = str(getattr(bm.block_aval, "memory_space", None)) == "smem"
        pinned = blk >= full and not smem
        operands.append({"shape": shape, "dtype": str(aval.dtype),
                         "full_bytes": full, "block_bytes": blk,
                         "pinned": pinned, "smem": smem,
                         "is_output": i >= len(avals) - n_out})
        if smem:
            smem_bytes += full
            continue
        block_bytes += blk
        if pinned:
            pinned_bytes += full
    prefetch_bytes = sum(
        int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize
        for a in prefetch)
    return {"grid": grid, "operands": operands, "pinned_bytes": pinned_bytes,
            "block_bytes": block_bytes, "prefetch_bytes": prefetch_bytes,
            "smem_bytes": smem_bytes, "n_prefetch": max(n_prefetch, 0)}


def pack_resident_bytes(eqn) -> int:
    """VMEM-resident bytes of the *pack* operands of one kernel launch: every
    pinned VMEM plane (metadata comparator planes, value/code vectors), with
    the activation tiles excluded.

    The activation input and the output(s) share the kernel's tile shape (the
    output avals); with grid==1 their blocks cover the full array and would
    masquerade as pinned — any pinned operand whose shape matches an output
    aval's shape is dropped, which removes exactly x2d and the outputs and
    leaves the pack planes (metadata rows are (F, n) shapes; value/code
    vectors are (1, M); the table_pack values plane is (ceil(M/128), 128))."""
    fp = pallas_footprint(eqn)
    tile_shapes = {op["shape"] for op in fp["operands"] if op["is_output"]}
    # scalar-prefetch rows (routed fn_ids etc.) and SMEM metadata operands
    # live in scalar memory — they are not part of the VMEM residency budget
    total = 0
    for op in fp["operands"]:
        if op["pinned"] and op["shape"] not in tile_shapes:
            total += op["full_bytes"]
    return total


# --------------------------------------------------------------------------------------
# Rule 5 — structural identity
# --------------------------------------------------------------------------------------

def trace(fn: Callable, *args, **kwargs):
    """``jax.make_jaxpr`` with kwargs folded in (trace only — never executes)."""
    if kwargs:
        return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return jax.make_jaxpr(fn)(*args)


# custom_jvp params print helper thunks with their memory address
# (``jvp_jaxpr_thunk=<function ... at 0x7f...>``); identical graphs from two
# builds differ only there, so addresses are masked out of the fingerprint.
_ADDR_RE = re.compile(r"0x[0-9a-f]+")


def fingerprint(fn: Callable, *args, **kwargs) -> str:
    """Canonical structural fingerprint of a closure: the printed jaxpr with
    object addresses masked.

    ``make_jaxpr`` names variables deterministically, so two closures print
    identically iff they trace to the same graph — the obs-off zero-overhead
    contract in one string comparison.
    """
    return _ADDR_RE.sub("0x_", str(trace(fn, *args, **kwargs)))


def structurally_identical(fn_a: Callable, fn_b: Callable, *args) -> bool:
    return fingerprint(fn_a, *args) == fingerprint(fn_b, *args)
