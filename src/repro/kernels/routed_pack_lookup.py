"""Routed Pallas kernels: per-row DYNAMIC fn_id dispatch via scalar prefetch.

The static pack kernels (``table_pack_lookup``) bake ``fn_id`` into the trace,
so a batch mixing functions — MoE-style routed activations, heterogeneous
serve traffic — needs one compiled executable per member.  Here the per-row
``fn_ids`` vector is a RUNTIME operand instead: ``pltpu.PrefetchScalarGridSpec``
prefetches it (plus the per-member interval counts / ragged offsets) into SMEM
before the grid runs, and

  * for the f32 :class:`TablePack`, the metadata BlockSpec *index maps* read
    ``fn_ids[i]`` to choose which (F, n_max) plane row is DMA'd into VMEM for
    grid row i — the scalar prefetch literally steers the DMA, the kernel body
    is the static body with a dynamic interval count;
  * for the :class:`QuantTablePack`, the ragged flat lanes stay whole-pinned
    in VMEM and the prefetched ``bounds_offsets`` / ``lane_offsets`` /
    ``entry_bits`` scalars (``pack.routing_scalars()``) index a member's lane
    segment and width group at runtime — gathers at ``offset + j`` replace the
    python-slice-at-trace-time of the static kernel, and both code vectors are
    gathered with the live one selected per row.

Grid geometry: one grid row per input row (the routing granularity), columns
blocked at ``block_cols``.  Because ``fn_ids`` (and the per-member flag
vectors) are runtime operands, RE-ROUTING NEVER RECOMPILES: one executable
serves every assignment of functions to rows, collapsing F specializations
into one.

Bit-parity contract (tests/test_routed_pack.py, tests/test_properties.py):
row i of every routed output is bit-identical under jit to the static-fn_id
dispatch of member ``fn_ids[i]`` — the kernel bodies run the same f32
compare/gather/FMA sequence as the static kernels, with the static python
branches (interval count, extrapolate, codes width) replaced by value-equal
dynamic selects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.approx.table_pack import (PolyTablePack, QuantTablePack,
                                     ShardedTablePack, TablePack, poly_horner,
                                     poly_horner_d1, resolve_fn_ids,
                                     routed_extr_flags)

from .table_lookup import resolve_interpret

DEFAULT_BLOCK_COLS = 65536  # (1, 65536) f32 tile = 256 KiB in + 256 KiB out


def tile_routed_rows(x: jax.Array, block_cols: int):
    """Flatten trailing dims and zero-pad columns for the routed grid.

    Rows are the routing granularity and stay unpadded (the grid is exactly
    (R, C_pad/block)); only columns pad to a lane multiple.  Returns
    ``(x2d, block, C)`` with ``block`` the largest 128-multiple column block
    <= ``block_cols`` that tiles ``C_pad``.
    """
    if x.ndim < 1:
        raise ValueError("routed dispatch needs a leading row axis (one "
                         "function id per row); got a 0-d input")
    flat = x.reshape(x.shape[0], -1)
    c = flat.shape[1]
    cpad = -(-c // 128) * 128
    block = min(-(-block_cols // 128) * 128, cpad)
    cpad = -(-cpad // block) * block
    if cpad != c:
        flat = jnp.pad(flat, ((0, 0), (0, cpad - c)))
    return flat, block, c


def _untile_rows(out2d: jax.Array, c: int, shape) -> jax.Array:
    return out2d[:, :c].reshape(shape)


# --------------------------------------------------------------------------------------
# f32 TablePack: prefetched fn_ids steer the metadata-row DMA.
# --------------------------------------------------------------------------------------


def _routed_select(x, brow, invd_row, base_row, segs_row, nf):
    """The static comparator plane + gathers with a DYNAMIC interval count.

    Same ops as ``table_lookup.select_params`` on the fn_ids-selected padded
    row: +inf padding never compares true, so the unclipped count ``ju`` only
    needs the dynamic ``min(ju, nf - 1)`` clip.  Returns ``ju`` too — the
    grad kernel derives the domain test ``x < b_nf`` from it (``ju < nf``)
    without a dynamic VMEM read.
    """
    ju = jnp.sum((x[..., None] >= brow[1:]).astype(jnp.int32), axis=-1)
    j = jnp.minimum(ju, nf - 1)
    p = jnp.take(brow, j, axis=0, mode="clip")
    invd = jnp.take(invd_row, j, axis=0, mode="clip")
    base = jnp.take(base_row, j, axis=0, mode="clip")
    segs = jnp.take(segs_row, j, axis=0, mode="clip")
    return ju, p, invd, base, segs


def _routed_kernel(ids_ref, n_ref, extr_ref, x_ref, bounds_ref, invd_ref,
                   base_ref, segs_ref, values_ref, o_ref):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf = n_ref[fid]
    extr = extr_ref[fid]
    x = x_ref[...].astype(jnp.float32)

    # the BlockSpec index map already DMA'd member fid's metadata row here
    _, p, invd, base, segs = _routed_select(
        x, bounds_ref[0, :], invd_ref[0, :], base_ref[0, :], segs_ref[0, :], nf)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)

    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    t = u - i
    t = jnp.where(extr > 0, t, jnp.clip(t, 0.0, 1.0))
    o_ref[...] = (y0 + t * (y1 - y0)).astype(o_ref.dtype)


def _routed_grad_kernel(ids_ref, n_ref, extr_ref, x_ref, bounds_ref, invd_ref,
                        base_ref, segs_ref, values_ref, y_ref, dy_ref):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf = n_ref[fid]
    extr = extr_ref[fid]
    x = x_ref[...].astype(jnp.float32)

    brow = bounds_ref[0, :]
    ju, p, invd, base, segs = _routed_select(
        x, brow, invd_ref[0, :], base_ref[0, :], segs_ref[0, :], nf)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    t = u - i
    slope = (y1 - y0) * invd
    inside = ((x >= brow[0]) & (ju < nf)).astype(jnp.float32)
    t = jnp.where(extr > 0, t, jnp.clip(t, 0.0, 1.0))
    slope = jnp.where(extr > 0, slope, slope * inside)
    y_ref[...] = (y0 + t * (y1 - y0)).astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


def _routed_grid_spec(x2d, n_max: int, values_shape, block_cols: int,
                      n_outs: int, num_scalars: int, pinned_meta: bool,
                      extra_pinned=(), n_meta_rows: int = 3):
    """PrefetchScalarGridSpec shared by the routed entry points.

    ``pinned_meta=False`` (f32 pack): the metadata planes — the boundary row
    plus ``n_meta_rows`` (F, n_max) planes (3 for the replicated pack, 4 for
    the sharded pack, which adds the ownership plane) — are streamed per grid
    row with ``fn_ids[i]`` as the DMA row index.  ``pinned_meta=True`` (quant
    pack): the ragged flat lanes stay whole-resident and the kernel indexes
    them with prefetched offsets.
    """
    rows, cpad = x2d.shape

    def row_map(i, j, *_):
        return (i, j)

    def fid_map(i, j, ids, *_):
        return (ids[i], 0)

    def pin_map(i, j, *_):
        return (0, 0)

    x_spec = pl.BlockSpec((1, block_cols), row_map)
    if pinned_meta:
        in_specs = [x_spec] + [pl.BlockSpec(s, pin_map) for s in extra_pinned]
    else:
        in_specs = ([x_spec, pl.BlockSpec((1, n_max + 1), fid_map)] +
                    [pl.BlockSpec((1, n_max), fid_map)] * n_meta_rows +
                    [pl.BlockSpec(values_shape, pin_map)])
    out_spec = pl.BlockSpec((1, block_cols), row_map)
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalars,
        grid=(rows, cpad // block_cols),
        in_specs=in_specs,
        out_specs=out_spec if n_outs == 1 else [out_spec] * n_outs,
    )


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret",
                                             "n_max", "grad"))
def _routed_call(ids, n_arr, extr_arr, x2d, bounds, invd, base, segs, values,
                 *, block_cols, interpret, n_max, grad):
    n_outs = 2 if grad else 1
    grid_spec = _routed_grid_spec(x2d, n_max, values.shape, block_cols,
                                  n_outs, num_scalars=3, pinned_meta=False)
    kernel = _routed_grad_kernel if grad else _routed_kernel
    out_shape = jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if not grad else [out_shape] * 2,
        interpret=interpret,
    )(ids, n_arr, extr_arr, x2d, bounds, invd, base, segs, values)


def _routed_prep(pack, fn_ids, x, extrapolate, block_cols, interpret):
    interpret = resolve_interpret(interpret)
    x2d, block, c = tile_routed_rows(x, block_cols)
    ids = resolve_fn_ids(pack, fn_ids, x2d.shape[0])
    extr = jnp.asarray(routed_extr_flags(pack, extrapolate))
    return x2d, block, c, ids, extr, interpret


def routed_pack_lookup_pallas(
    pack: TablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
) -> jax.Array:
    """Row i of ``x`` through member ``fn_ids[i]`` — one executable for every
    routing.  ``fn_ids``: names/ints (validated) or a traced int vector."""
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr,) = pack.routing_scalars()
    out = _routed_call(
        ids, jnp.asarray(n_arr), extr, x2d, pack.boundaries, pack.inv_delta,
        pack.base, pack.seg_count, pack.values.reshape(1, -1),
        block_cols=block, interpret=interpret, n_max=pack.n_max, grad=False)
    return _untile_rows(out, c, x.shape)


def routed_pack_grad_pallas(
    pack: TablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
):
    """Routed (y, dy/dx) in one fused selector pass per row."""
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr,) = pack.routing_scalars()
    y2d, dy2d = _routed_call(
        ids, jnp.asarray(n_arr), extr, x2d, pack.boundaries, pack.inv_delta,
        pack.base, pack.seg_count, pack.values.reshape(1, -1),
        block_cols=block, interpret=interpret, n_max=pack.n_max, grad=True)
    return _untile_rows(y2d, c, x.shape), _untile_rows(dy2d, c, x.shape)


# --------------------------------------------------------------------------------------
# QuantTablePack: prefetched ragged offsets + runtime width-group select.
# --------------------------------------------------------------------------------------


def _routed_quant_select(x, bounds, invd, base, segs, scale, zero, ramp,
                         bo, lo, nf, n_max: int):
    """Masked comparator over the fid's ragged lane segment + seven gathers.

    The static kernel slices ``[bo : bo + n]`` at trace time; here ``bo``/
    ``lo`` are runtime scalars, so the comparator gathers the boundary row at
    ``bo + m`` and masks lanes past the member's real count (they belong to
    the NEXT member and would otherwise compare true).  All parameter gathers
    hit exactly the static kernel's elements: ``lane[lo + j]``.
    """
    m = jax.lax.broadcasted_iota(jnp.int32, (1, n_max), 1) + 1  # (1, n_max)
    bvals = jnp.take(bounds, bo + m[0], axis=0, mode="clip")  # (n_max,)
    cmp = (x[..., None] >= bvals) & (m[0] <= nf)
    ju = jnp.sum(cmp.astype(jnp.int32), axis=-1)
    j = jnp.minimum(ju, nf - 1)
    p = jnp.take(bounds, bo + j, axis=0, mode="clip")
    gl = lo + j
    return (ju, p,
            jnp.take(invd, gl, axis=0, mode="clip"),
            jnp.take(base, gl, axis=0, mode="clip"),
            jnp.take(segs, gl, axis=0, mode="clip"),
            jnp.take(scale, gl, axis=0, mode="clip"),
            jnp.take(zero, gl, axis=0, mode="clip"),
            jnp.take(ramp, gl, axis=0, mode="clip"))


def _gather_codes(codes8_ref, codes16_ref, a, bits):
    """Adjacent-pair gather from BOTH width groups, live one selected per row
    (the static kernel's python-time ``codes_for(fid)`` made dynamic)."""
    c8 = jnp.take(codes8_ref[0, :], a, axis=0, mode="clip").astype(jnp.float32)
    c16 = jnp.take(codes16_ref[0, :], a, axis=0,
                   mode="clip").astype(jnp.float32)
    return jnp.where(bits == 8, c8, c16)


def _routed_quant_kernel(ids_ref, n_ref, extr_ref, bo_ref, lo_ref, bits_ref,
                         x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                         scale_ref, zero_ref, ramp_ref, codes8_ref,
                         codes16_ref, o_ref, *, n_max: int):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf, extr = n_ref[fid], extr_ref[fid]
    bo, lo, bits = bo_ref[fid], lo_ref[fid], bits_ref[fid]
    x = x_ref[...].astype(jnp.float32)

    _, p, invd, base, segs, scale, zero, ramp = _routed_quant_select(
        x, bounds_ref[0, :], invd_ref[0, :], base_ref[0, :], segs_ref[0, :],
        scale_ref[0, :], zero_ref[0, :], ramp_ref[0, :], bo, lo, nf, n_max)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    c0 = _gather_codes(codes8_ref, codes16_ref, a, bits)
    c1 = _gather_codes(codes8_ref, codes16_ref, a + 1, bits)

    r_ = zero + ramp * i  # dequantize-on-read: chord ramp + scaled code
    y0 = r_ + scale * c0
    y1 = (r_ + ramp) + scale * c1

    t = u - i
    t = jnp.where(extr > 0, t, jnp.clip(t, 0.0, 1.0))
    o_ref[...] = (y0 + t * (y1 - y0)).astype(o_ref.dtype)


def _routed_quant_grad_kernel(ids_ref, n_ref, extr_ref, bo_ref, lo_ref,
                              bits_ref, x_ref, bounds_ref, invd_ref, base_ref,
                              segs_ref, scale_ref, zero_ref, ramp_ref,
                              codes8_ref, codes16_ref, y_ref, dy_ref, *,
                              n_max: int):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf, extr = n_ref[fid], extr_ref[fid]
    bo, lo, bits = bo_ref[fid], lo_ref[fid], bits_ref[fid]
    x = x_ref[...].astype(jnp.float32)

    bounds = bounds_ref[0, :]
    ju, p, invd, base, segs, scale, zero, ramp = _routed_quant_select(
        x, bounds, invd_ref[0, :], base_ref[0, :], segs_ref[0, :],
        scale_ref[0, :], zero_ref[0, :], ramp_ref[0, :], bo, lo, nf, n_max)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    c0 = _gather_codes(codes8_ref, codes16_ref, a, bits)
    c1 = _gather_codes(codes8_ref, codes16_ref, a + 1, bits)

    r_ = zero + ramp * i
    y0 = r_ + scale * c0
    y1 = (r_ + ramp) + scale * c1

    t = u - i
    slope = (ramp + scale * (c1 - c0)) * invd
    p0 = jnp.take(bounds, bo, axis=0, mode="clip")
    inside = ((x >= p0) & (ju < nf)).astype(jnp.float32)
    t = jnp.where(extr > 0, t, jnp.clip(t, 0.0, 1.0))
    slope = jnp.where(extr > 0, slope, slope * inside)
    y_ref[...] = (y0 + t * (y1 - y0)).astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret",
                                             "n_max", "grad"))
def _routed_quant_call(ids, n_arr, extr_arr, bo_arr, lo_arr, bits_arr, x2d,
                       bounds, invd, base, segs, scale, zero, ramp, codes8,
                       codes16, *, block_cols, interpret, n_max, grad):
    operands = (bounds, invd, base, segs, scale, zero, ramp, codes8, codes16)
    n_outs = 2 if grad else 1
    grid_spec = _routed_grid_spec(
        x2d, n_max, None, block_cols, n_outs, num_scalars=6, pinned_meta=True,
        extra_pinned=[a.shape for a in operands])
    kernel = functools.partial(
        _routed_quant_grad_kernel if grad else _routed_quant_kernel,
        n_max=n_max)
    out_shape = jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if not grad else [out_shape] * 2,
        interpret=interpret,
    )(ids, n_arr, extr_arr, bo_arr, lo_arr, bits_arr, x2d, *operands)


def _quant_routed_args(pack: QuantTablePack):
    scalars = tuple(jnp.asarray(s) for s in pack.routing_scalars())
    operands = (pack.boundaries.reshape(1, -1), pack.inv_delta.reshape(1, -1),
                pack.base.reshape(1, -1), pack.seg_count.reshape(1, -1),
                pack.scale.reshape(1, -1), pack.zero.reshape(1, -1),
                pack.ramp.reshape(1, -1), pack.codes8.reshape(1, -1),
                pack.codes16.reshape(1, -1))
    n_max = int(np.max(pack.n_intervals))
    return scalars, operands, n_max


def routed_quant_pack_lookup_pallas(
    pack: QuantTablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
) -> jax.Array:
    """Routed dequantize-on-read: row i through quantized member fn_ids[i]."""
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr, bo_arr, lo_arr, bits_arr), operands, n_max = \
        _quant_routed_args(pack)
    out = _routed_quant_call(
        ids, n_arr, extr, bo_arr, lo_arr, bits_arr, x2d, *operands,
        block_cols=block, interpret=interpret, n_max=n_max, grad=False)
    return _untile_rows(out, c, x.shape)


def routed_quant_pack_grad_pallas(
    pack: QuantTablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
):
    """Routed quantized (y, dy/dx) in one fused selector pass per row."""
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr, bo_arr, lo_arr, bits_arr), operands, n_max = \
        _quant_routed_args(pack)
    y2d, dy2d = _routed_quant_call(
        ids, n_arr, extr, bo_arr, lo_arr, bits_arr, x2d, *operands,
        block_cols=block, interpret=interpret, n_max=n_max, grad=True)
    return _untile_rows(y2d, c, x.shape), _untile_rows(dy2d, c, x.shape)


# --------------------------------------------------------------------------------------
# ShardedTablePack: routed dispatch over ONE shard's values slice, unowned masked.
# --------------------------------------------------------------------------------------
#
# Same scalar-prefetch dispatch as the f32 routed kernels — fn_ids steer the
# metadata-row DMA — but the values operand is one SHARD's padded slice, the
# base plane holds shard-local rebased addresses, and a fourth streamed plane
# (the ownership mask, gathered at the selected sub-interval like the other
# parameters) zeroes rows of elements the shard does not own.  Per-shard
# outputs sum to the replicated routed result bit for bit (one owner + zeros),
# so ONE executable still serves every routing — per shard.


def _sharded_routed_kernel(ids_ref, n_ref, extr_ref, x_ref, bounds_ref,
                           invd_ref, lbase_ref, segs_ref, own_ref, values_ref,
                           o_ref):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf = n_ref[fid]
    extr = extr_ref[fid]
    x = x_ref[...].astype(jnp.float32)

    ju, p, invd, base, segs = _routed_select(
        x, bounds_ref[0, :], invd_ref[0, :], lbase_ref[0, :], segs_ref[0, :],
        nf)
    j = jnp.minimum(ju, nf - 1)
    own = jnp.take(own_ref[0, :], j, axis=0, mode="clip")

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)  # SHARD-LOCAL (rebased at plan time)

    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    t = u - i
    t = jnp.where(extr > 0, t, jnp.clip(t, 0.0, 1.0))
    y = y0 + t * (y1 - y0)
    o_ref[...] = jnp.where(own > 0, y, 0.0).astype(o_ref.dtype)


def _sharded_routed_grad_kernel(ids_ref, n_ref, extr_ref, x_ref, bounds_ref,
                                invd_ref, lbase_ref, segs_ref, own_ref,
                                values_ref, y_ref, dy_ref):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf = n_ref[fid]
    extr = extr_ref[fid]
    x = x_ref[...].astype(jnp.float32)

    brow = bounds_ref[0, :]
    ju, p, invd, base, segs = _routed_select(
        x, brow, invd_ref[0, :], lbase_ref[0, :], segs_ref[0, :], nf)
    j = jnp.minimum(ju, nf - 1)
    own = jnp.take(own_ref[0, :], j, axis=0, mode="clip")

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    t = u - i
    slope = (y1 - y0) * invd
    inside = ((x >= brow[0]) & (ju < nf)).astype(jnp.float32)
    t = jnp.where(extr > 0, t, jnp.clip(t, 0.0, 1.0))
    slope = jnp.where(extr > 0, slope, slope * inside)
    y_ref[...] = jnp.where(own > 0, y0 + t * (y1 - y0), 0.0).astype(y_ref.dtype)
    dy_ref[...] = jnp.where(own > 0, slope, 0.0).astype(dy_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret",
                                             "n_max", "grad"))
def _sharded_routed_call(ids, n_arr, extr_arr, x2d, bounds, invd, lbase, segs,
                         own, values, *, block_cols, interpret, n_max, grad):
    n_outs = 2 if grad else 1
    grid_spec = _routed_grid_spec(x2d, n_max, values.shape, block_cols,
                                  n_outs, num_scalars=3, pinned_meta=False,
                                  n_meta_rows=4)
    kernel = _sharded_routed_grad_kernel if grad else _sharded_routed_kernel
    out_shape = jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if not grad else [out_shape] * 2,
        interpret=interpret,
    )(ids, n_arr, extr_arr, x2d, bounds, invd, lbase, segs, own, values)


def _sharded_routed_sum(pack: ShardedTablePack, fn_ids, x, extrapolate,
                        block_cols, interpret, grad: bool):
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr,) = pack.routing_scalars()
    n_arr = jnp.asarray(n_arr)
    outs = None
    for s in range(pack.n_shards):
        o = _sharded_routed_call(
            ids, n_arr, extr, x2d, pack.boundaries, pack.inv_delta,
            pack.local_base[s], pack.seg_count, pack.owned[s],
            pack.values[s].reshape(1, -1),
            block_cols=block, interpret=interpret, n_max=pack.n_max, grad=grad)
        if not grad:
            o = (o,)
        outs = o if outs is None else tuple(a + b for a, b in zip(outs, o))
    return tuple(_untile_rows(o, c, x.shape) for o in outs)


def sharded_routed_pack_lookup_pallas(
    pack: ShardedTablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
) -> jax.Array:
    """Row i of ``x`` through member ``fn_ids[i]`` of the SHARDED pack — one
    routed executable per shard, contributions summed (off-mesh path)."""
    (y,) = _sharded_routed_sum(pack, fn_ids, x, extrapolate, block_cols,
                               interpret, grad=False)
    return y


def sharded_routed_pack_grad_pallas(
    pack: ShardedTablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
):
    """Routed sharded (y, dy/dx) — per-shard fused passes, summed."""
    return _sharded_routed_sum(pack, fn_ids, x, extrapolate, block_cols,
                               interpret, grad=True)


# --------------------------------------------------------------------------------------
# PolyTablePack: routed Horner over lane-padded mixed-degree / mixed-width cells.
# --------------------------------------------------------------------------------------
#
# Same prefetch dispatch as the quant routed kernels, with two extra runtime
# scalars per member: ``stride`` (= degree+1, the cell width in the code
# vectors) and a THREE-way width-group select (int8 / int16 / raw f32).  Every
# row runs a uniform ``lmax``-lane Horner: padded metadata lanes dequantize to
# exactly 0.0, and leading zero coefficients pass through Horner as
# ``0*t + c = c``, so the uniform loop is bit-identical to each member's own
# degree-L evaluation.


def _routed_poly_select(x, bounds, invd, base, segs, bo, lo, nf, n_max: int):
    """Masked comparator over the fid's lane segment + four selector gathers
    (the quant select minus the single-lane dequant params — poly dequant is
    per LANE and happens in the coefficient loop)."""
    m = jax.lax.broadcasted_iota(jnp.int32, (1, n_max), 1) + 1  # (1, n_max)
    bvals = jnp.take(bounds, bo + m[0], axis=0, mode="clip")  # (n_max,)
    cmp = (x[..., None] >= bvals) & (m[0] <= nf)
    ju = jnp.sum(cmp.astype(jnp.int32), axis=-1)
    j = jnp.minimum(ju, nf - 1)
    p = jnp.take(bounds, bo + j, axis=0, mode="clip")
    gl = lo + j
    return (ju, gl, p,
            jnp.take(invd, gl, axis=0, mode="clip"),
            jnp.take(base, gl, axis=0, mode="clip"),
            jnp.take(segs, gl, axis=0, mode="clip"))


def _gather_poly_codes(codes8_ref, codes16_ref, codes32_ref, a, bits):
    """Gather from all THREE width groups, live one selected per row (the
    static kernel's python-time ``codes_for(fid)`` made dynamic; f32 members
    store raw coefficients, so the 32-bit group needs no cast)."""
    c8 = jnp.take(codes8_ref[0, :], a, axis=0, mode="clip").astype(jnp.float32)
    c16 = jnp.take(codes16_ref[0, :], a, axis=0,
                   mode="clip").astype(jnp.float32)
    c32 = jnp.take(codes32_ref[0, :], a, axis=0, mode="clip")
    return jnp.where(bits == 8, c8, jnp.where(bits == 16, c16, c32))


def _routed_poly_coeffs(gl, i, base, bits, stride_f, zero_ref, ramp_ref,
                        scale_ref, codes8_ref, codes16_ref, codes32_ref, *,
                        lmax: int):
    """Uniform ``lmax`` lane-padded coefficient gather + dequant.

    Metadata lane l of global member cell ``gl`` lives at flat
    ``gl*lmax + l``; code lane l of sub-interval i at ``base + i*stride + l``
    (addresses past a member's real cell may alias neighbours, but the padded
    lane's (zero, ramp, scale) = (0, 0, 0) dequantizes them to exactly 0.0).
    """
    cs = []
    for l in range(lmax):
        gm = gl * lmax + l
        zl = jnp.take(zero_ref[0, :], gm, axis=0, mode="clip")
        rl = jnp.take(ramp_ref[0, :], gm, axis=0, mode="clip")
        sl = jnp.take(scale_ref[0, :], gm, axis=0, mode="clip")
        a = (base + i * stride_f + float(l)).astype(jnp.int32)
        q = _gather_poly_codes(codes8_ref, codes16_ref, codes32_ref, a, bits)
        cs.append((zl + rl * i) + sl * q)
    return cs


def _routed_poly_kernel(ids_ref, n_ref, extr_ref, bo_ref, lo_ref, bits_ref,
                        stride_ref, x_ref, bounds_ref, invd_ref, base_ref,
                        segs_ref, zero_ref, ramp_ref, scale_ref, codes8_ref,
                        codes16_ref, codes32_ref, o_ref, *, n_max: int,
                        lmax: int):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf, extr = n_ref[fid], extr_ref[fid]
    bo, lo, bits = bo_ref[fid], lo_ref[fid], bits_ref[fid]
    stride_f = stride_ref[fid].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)

    _, gl, p, invd, base, segs = _routed_poly_select(
        x, bounds_ref[0, :], invd_ref[0, :], base_ref[0, :], segs_ref[0, :],
        bo, lo, nf, n_max)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    cs = _routed_poly_coeffs(gl, i, base, bits, stride_f, zero_ref, ramp_ref,
                             scale_ref, codes8_ref, codes16_ref, codes32_ref,
                             lmax=lmax)

    t = u - i
    tc = jnp.clip(t, 0.0, 1.0)
    y = poly_horner(cs, tc)
    ye = y + poly_horner_d1(cs, tc) * (t - tc)
    o_ref[...] = jnp.where(extr > 0, ye, y).astype(o_ref.dtype)


def _routed_poly_grad_kernel(ids_ref, n_ref, extr_ref, bo_ref, lo_ref,
                             bits_ref, stride_ref, x_ref, bounds_ref, invd_ref,
                             base_ref, segs_ref, zero_ref, ramp_ref, scale_ref,
                             codes8_ref, codes16_ref, codes32_ref, y_ref,
                             dy_ref, *, n_max: int, lmax: int):
    r = pl.program_id(0)
    fid = ids_ref[r]
    nf, extr = n_ref[fid], extr_ref[fid]
    bo, lo, bits = bo_ref[fid], lo_ref[fid], bits_ref[fid]
    stride_f = stride_ref[fid].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)

    bounds = bounds_ref[0, :]
    ju, gl, p, invd, base, segs = _routed_poly_select(
        x, bounds, invd_ref[0, :], base_ref[0, :], segs_ref[0, :],
        bo, lo, nf, n_max)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    cs = _routed_poly_coeffs(gl, i, base, bits, stride_f, zero_ref, ramp_ref,
                             scale_ref, codes8_ref, codes16_ref, codes32_ref,
                             lmax=lmax)

    t = u - i
    tc = jnp.clip(t, 0.0, 1.0)
    y = poly_horner(cs, tc)
    g = poly_horner_d1(cs, tc)
    slope = g * invd
    p0 = jnp.take(bounds, bo, axis=0, mode="clip")
    inside = ((x >= p0) & (ju < nf)).astype(jnp.float32)
    y_ref[...] = jnp.where(extr > 0, y + g * (t - tc), y).astype(y_ref.dtype)
    dy_ref[...] = jnp.where(extr > 0, slope, slope * inside).astype(dy_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret",
                                             "n_max", "lmax", "grad"))
def _routed_poly_call(ids, n_arr, extr_arr, bo_arr, lo_arr, bits_arr,
                      stride_arr, x2d, bounds, invd, base, segs, zero, ramp,
                      scale, codes8, codes16, codes32, *, block_cols,
                      interpret, n_max, lmax, grad):
    operands = (bounds, invd, base, segs, zero, ramp, scale, codes8, codes16,
                codes32)
    n_outs = 2 if grad else 1
    grid_spec = _routed_grid_spec(
        x2d, n_max, None, block_cols, n_outs, num_scalars=7, pinned_meta=True,
        extra_pinned=[a.shape for a in operands])
    kernel = functools.partial(
        _routed_poly_grad_kernel if grad else _routed_poly_kernel,
        n_max=n_max, lmax=lmax)
    out_shape = jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if not grad else [out_shape] * 2,
        interpret=interpret,
    )(ids, n_arr, extr_arr, bo_arr, lo_arr, bits_arr, stride_arr, x2d,
      *operands)


def _poly_routed_args(pack: PolyTablePack):
    scalars = tuple(jnp.asarray(s) for s in pack.routing_scalars())
    operands = (pack.boundaries.reshape(1, -1), pack.inv_delta.reshape(1, -1),
                pack.base.reshape(1, -1), pack.seg_count.reshape(1, -1),
                pack.zero.reshape(1, -1), pack.ramp.reshape(1, -1),
                pack.scale.reshape(1, -1), pack.codes8.reshape(1, -1),
                pack.codes16.reshape(1, -1), pack.codes32.reshape(1, -1))
    n_max = int(np.max(pack.n_intervals))
    return scalars, operands, n_max


def routed_poly_pack_lookup_pallas(
    pack: PolyTablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
) -> jax.Array:
    """Routed Horner-on-read: row i through planner-chosen member fn_ids[i]."""
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr, bo_arr, lo_arr, bits_arr, stride_arr), operands, n_max = \
        _poly_routed_args(pack)
    out = _routed_poly_call(
        ids, n_arr, extr, bo_arr, lo_arr, bits_arr, stride_arr, x2d, *operands,
        block_cols=block, interpret=interpret, n_max=n_max,
        lmax=pack.max_lanes, grad=False)
    return _untile_rows(out, c, x.shape)


def routed_poly_pack_grad_pallas(
    pack: PolyTablePack,
    fn_ids,
    x: jax.Array,
    *,
    extrapolate=False,
    block_cols: int = DEFAULT_BLOCK_COLS,
    interpret: bool | None = None,
):
    """Routed poly (y, dy/dx) in one fused selector + Horner pass per row."""
    x2d, block, c, ids, extr, interpret = _routed_prep(
        pack, fn_ids, x, extrapolate, block_cols, interpret)
    (n_arr, bo_arr, lo_arr, bits_arr, stride_arr), operands, n_max = \
        _poly_routed_args(pack)
    y2d, dy2d = _routed_poly_call(
        ids, n_arr, extr, bo_arr, lo_arr, bits_arr, stride_arr, x2d, *operands,
        block_cols=block, interpret=interpret, n_max=n_max,
        lmax=pack.max_lanes, grad=True)
    return _untile_rows(y2d, c, x.shape), _untile_rows(dy2d, c, x.shape)
