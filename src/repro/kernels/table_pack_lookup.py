"""Fused multi-function Pallas kernels over a :class:`repro.approx.TablePack`.

One packed values vector stays VMEM-resident as a (ceil(M/128), 128) plane,
with the (F, n_max) metadata planes in SMEM — BRAM instantiation at the
function-set level — and a single kernel body serves ANY member function: the
static ``fn_id`` picks the metadata row at trace time (zero runtime cost; the
scalar reads lower to constant offsets), then the shared comparator-plane
selector (``table_lookup.select_params``) and the adjacent-pair read
(``gather_pair``: per plane row, an in-vreg lane gather and a row select) run.
This is the form the TPU compiler (Mosaic) accepts; the other kernels in this
module still gather from a 1-D VMEM row and compile only in interpret mode.
Because every specialization shares the same operand shapes and the same pack
arrays, switching functions costs one cached-executable lookup instead of a
new table upload, and the VMEM working set is ONE pack instead of F separate
tables.

Two entry points mirror the per-table pair:

  * ``table_pack_lookup_pallas``  — value only (serving path);
  * ``table_pack_grad_pallas``    — fused value + slope in one selector pass
    (training path; used by ``make_pack_fn``'s custom_jvp).

Both validated bit-identical against ``repro.approx.table_pack.eval_pack_ref``
/ ``eval_pack_slope`` in interpret mode (tests/test_table_pack.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.approx.jax_table import select_interval
from repro.approx.table_pack import (PolyTablePack, QuantTablePack,
                                     ShardedTablePack, TablePack, poly_horner,
                                     poly_horner_d1)
from repro.core.range_reduce import (exp_edges, exp_fold, exp_reconstruct,
                                     log_edges, log_fold, log_reconstruct,
                                     trig_edges, trig_fold, trig_reconstruct,
                                     trig_slope_reconstruct)

from .table_lookup import (DEFAULT_BLOCK_ROWS, LANE, _pinned, resolve_interpret,
                           select_params, tile_activations, untile_activations)


VREG_LANES = 128  # lanes of one vreg: the width the in-vreg gather reads


def values_plane(values: jax.Array) -> jax.Array:
    """The packed ``(M,)`` values as a zero-padded ``(ceil(M/128), 128)``
    plane: one row per vreg of lanes, the layout :func:`gather_pair` reads."""
    m = values.shape[0]
    rows = -(-m // VREG_LANES)
    return jnp.pad(values, (0, rows * VREG_LANES - m)).reshape(rows, VREG_LANES)


def member_span(pack: TablePack, fid: int) -> Tuple[int, int]:
    """Static ``(first, last)`` values index member ``fid`` reads: every
    adjacent pair ``(a, a + 1)`` of its sub-intervals lies inside."""
    n = pack.n_intervals[fid]
    base = np.asarray(pack.base)[fid]
    segs = np.asarray(pack.seg_count)[fid]
    return int(base[0]), int(base[n - 1] + segs[n - 1])


def gather_pair(plane_ref, a, span: Tuple[int, int]):
    """Adjacent-pair BRAM read: ``(values[a], values[a + 1])`` for an int32
    address tile ``a``, addresses clamped into the member's ``span`` (which
    every finite or infinite input's pair lies in).

    Mosaic gathers within one vreg only, so the tile is read 128 lanes at a
    time: each plane row the span covers is broadcast over the chunk and read
    with a lane gather (``take_along_axis``, lowered to an in-vreg dynamic
    gather); the row select keeps the read whose address lies in that row.
    Pure selection, so the pair is bit-identical to a 1-D gather from the
    values vector.
    """
    lo, hi = span
    pairs = []
    for c in range(0, a.shape[1], VREG_LANES):
        chunk = a[:, c:c + VREG_LANES]
        addrs = (jnp.clip(chunk, lo, hi), jnp.clip(chunk + 1, lo, hi))
        rows = [addr >> 7 for addr in addrs]  # VREG_LANES == 2**7
        lanes = [addr & (VREG_LANES - 1) for addr in addrs]
        ys = [jnp.zeros(chunk.shape, plane_ref.dtype) for _ in addrs]
        for r in range(lo // VREG_LANES, hi // VREG_LANES + 1):
            vals = jnp.broadcast_to(plane_ref[r:r + 1, :], chunk.shape)
            for k in range(2):
                got = jnp.take_along_axis(vals, lanes[k], axis=1,
                                          mode="promise_in_bounds")
                ys[k] = jnp.where(rows[k] == r, got, ys[k])
        pairs.append(ys)
    return [jnp.concatenate([p[k] for p in pairs], axis=1) for k in range(2)]


def _pack_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref, plane_ref,
                 o_ref, *, fn_id: int, n_intervals: int,
                 span: Tuple[int, int], extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)

    # static row pick: the ONE pack serves any function; fn_id costs nothing
    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, fn_id, n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)  # base is GLOBAL: offset baked in at pack time
    y0, y1 = gather_pair(plane_ref, a, span)

    t = u - i
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
    o_ref[...] = (y0 + t * (y1 - y0)).astype(o_ref.dtype)


def _pack_grad_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                      plane_ref, y_ref, dy_ref, *, fn_id: int,
                      n_intervals: int, span: Tuple[int, int],
                      extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)

    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, fn_id, n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    y0, y1 = gather_pair(plane_ref, a, span)

    t = u - i
    slope = (y1 - y0) * invd
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
        inside = ((x >= bounds_ref[fn_id, 0]) &
                  (x < bounds_ref[fn_id, n_intervals])).astype(jnp.float32)
        slope = slope * inside
    y_ref[...] = (y0 + t * (y1 - y0)).astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


def _pack_specs(x2d, pack_arrays, block_rows):
    rows, lane = x2d.shape
    in_specs = [pl.BlockSpec((block_rows, lane), lambda i: (i, 0))]
    in_specs += [_pinned(a.shape) for a in pack_arrays]
    return (rows // block_rows,), in_specs


def _plane_call(kernel, n_out, x2d, bounds, invd, base, segs, values, *,
                block_rows, interpret, **static):
    """Launch a ``table_pack`` kernel: selector metadata in SMEM (scalar
    reads), the values plane pinned in VMEM, activations streamed in
    ``(block_rows, lane)`` tiles."""
    rows, lane = x2d.shape
    if lane % VREG_LANES:
        raise ValueError(f"table_pack kernels tile {VREG_LANES}-lane chunks; "
                         f"lane={lane} is not a multiple")
    plane = values_plane(values)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((block_rows, lane), lambda i: (i, 0))
    out = jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)
    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid=(rows // block_rows,),
        in_specs=[tile, smem, smem, smem, smem, _pinned(plane.shape)],
        out_specs=tile if n_out == 1 else [tile] * n_out,
        out_shape=out if n_out == 1 else [out] * n_out,
        interpret=interpret,
    )(x2d, bounds, invd, base, segs, plane)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "fn_id", "n_intervals",
                              "span", "extrapolate"))
def _call(x2d, bounds, invd, base, segs, values, *, block_rows, interpret,
          fn_id, n_intervals, span, extrapolate):
    return _plane_call(_pack_kernel, 1, x2d, bounds, invd, base, segs, values,
                       block_rows=block_rows, interpret=interpret, fn_id=fn_id,
                       n_intervals=n_intervals, span=span,
                       extrapolate=extrapolate)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "fn_id", "n_intervals",
                              "span", "extrapolate"))
def _call_grad(x2d, bounds, invd, base, segs, values, *, block_rows, interpret,
               fn_id, n_intervals, span, extrapolate):
    return _plane_call(_pack_grad_kernel, 2, x2d, bounds, invd, base, segs,
                       values, block_rows=block_rows, interpret=interpret,
                       fn_id=fn_id, n_intervals=n_intervals, span=span,
                       extrapolate=extrapolate)


def _prep(pack: TablePack, fn, x, lane, block_rows, interpret):
    # member_id validates ints too: an out-of-range fn_id raises a KeyError
    # naming the pack members instead of an opaque tuple IndexError below
    fid = pack.member_id(fn)
    x2d, block, n = tile_activations(x, lane, block_rows)
    return fid, x2d, block, n, resolve_interpret(interpret)


def table_pack_lookup_pallas(
    pack: TablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Evaluate member ``fn`` (name or fn_id) of the pack over a tensor."""
    fid, x2d, block, n, interpret = _prep(pack, fn, x, lane, block_rows,
                                          interpret)
    out = _call(
        x2d, pack.boundaries, pack.inv_delta, pack.base, pack.seg_count,
        pack.values,
        block_rows=block, interpret=interpret, fn_id=fid,
        n_intervals=pack.n_intervals[fid], span=member_span(pack, fid),
        extrapolate=extrapolate,
    )
    return untile_activations(out, n, x.shape)


# --------------------------------------------------------------------------------------
# TableFlash kernel — flash attention's softmax exponent from the exp_neg member.
# --------------------------------------------------------------------------------------
#
# The running-softmax arguments (s - m_new, m - m_new) are <= 0 by construction
# but can sit at -2e38 for masked/pad key slots (NEG_INF - m).  The kernel fuses
# an UNDERFLOW-TO-ZERO tail in front of the standard selector: below the
# member's lo edge the result is exactly 0.0, matching f32 ``jnp.exp``'s own
# underflow for the hugely-negative masked-slot arguments — so masked, empty,
# and pad key slots carry weight 0 in BOTH the exact and the table path (a
# clamp-at-lo tail would instead give every masked slot a spurious exp(lo)
# ~ 1.1e-7 weight, which at decode's ring-buffer occupancy dominates E_a).
# The address math still clamps (``max(x, lo)``) so the ``(x - p) * inv_delta``
# product never sees a 1e38-magnitude operand; the zero-tail select happens on
# the RAW x afterwards.  Bit-identical to the jnp oracle under jit, asserted
# in tests/test_table_flash.py.


def _tableflash_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                       values_ref, o_ref, *, fn_id: int, n_intervals: int):
    x_raw = x_ref[...].astype(jnp.float32)
    lo = bounds_ref[fn_id, 0]
    x = jnp.maximum(x_raw, lo)  # address saturation only

    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, fn_id, n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)

    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    t = jnp.clip(u - i, 0.0, 1.0)  # saturate: exp_neg never extrapolates
    y = y0 + t * (y1 - y0)
    # underflow-to-zero tail: exp(z) < exp(lo) ~ 1.1e-7 rounds to 0, exactly
    # like the masked-slot exact path
    o_ref[...] = jnp.where(x_raw < lo, 0.0, y).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "fn_id", "n_intervals"))
def _tableflash_call(x2d, bounds, invd, base, segs, values, *, block_rows,
                     interpret, fn_id, n_intervals):
    grid, in_specs = _pack_specs(x2d, (bounds, invd, base, segs, values),
                                 block_rows)
    kernel = functools.partial(_tableflash_kernel, fn_id=fn_id,
                               n_intervals=n_intervals)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, bounds, invd, base, segs, values)


def tableflash_exp_pallas(
    pack: TablePack,
    x: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused clamp + exp_neg lookup over flash attention's exponent tensor."""
    fid, x2d, block, n, interpret = _prep(pack, "exp_neg", x, lane, block_rows,
                                          interpret)
    out = _tableflash_call(
        x2d, pack.boundaries, pack.inv_delta, pack.base, pack.seg_count,
        pack.values.reshape(1, -1),
        block_rows=block, interpret=interpret, fn_id=fid,
        n_intervals=pack.n_intervals[fid],
    )
    return untile_activations(out, n, x.shape)


# --------------------------------------------------------------------------------------
# QuantPack kernels — int8/int16 codes VMEM-resident, dequantized on read.
# --------------------------------------------------------------------------------------
#
# The quantized pack stores RAGGED metadata lanes (member fid's segment starts
# at a static offset — see QuantTablePack), so the kernels slice the lane refs
# with python-int bounds (free at trace time) instead of indexing an
# (F, n_max) plane row.  Dequantization adds three gathers (scale, zero, ramp
# — same selector index j) and one FMA per endpoint after the codes gather:
#
#     v = (zero + ramp * i) + scale * c
#
# The codes operand is int8 or int16 — chosen per member at pack-build time by
# the error-budget splitter — so the VMEM working set shrinks 2-4x vs the f32
# pack while the end-to-end |f - table| <= Ea contract still holds.


def _quant_select(x, bounds_ref, invd_ref, base_ref, segs_ref, scale_ref,
                  zero_ref, ramp_ref, *, bo: int, lo: int, n: int):
    """Comparator plane + seven gathers from member (bo, lo, n)'s ragged lanes."""
    brow = bounds_ref[0, bo : bo + n + 1]
    j = select_interval(brow, n, x)
    p = jnp.take(brow, j, axis=0, mode="clip")
    invd = jnp.take(invd_ref[0, lo : lo + n], j, axis=0, mode="clip")
    base = jnp.take(base_ref[0, lo : lo + n], j, axis=0, mode="clip")
    segs = jnp.take(segs_ref[0, lo : lo + n], j, axis=0, mode="clip")
    scale = jnp.take(scale_ref[0, lo : lo + n], j, axis=0, mode="clip")
    zero = jnp.take(zero_ref[0, lo : lo + n], j, axis=0, mode="clip")
    ramp = jnp.take(ramp_ref[0, lo : lo + n], j, axis=0, mode="clip")
    return p, invd, base, segs, scale, zero, ramp


def _quant_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref, scale_ref,
                  zero_ref, ramp_ref, codes_ref, o_ref, *, bo: int, lo: int,
                  n_intervals: int, extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)
    p, invd, base, segs, scale, zero, ramp = _quant_select(
        x, bounds_ref, invd_ref, base_ref, segs_ref, scale_ref, zero_ref,
        ramp_ref, bo=bo, lo=lo, n=n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)

    codes = codes_ref[0, :]
    c0 = jnp.take(codes, a, axis=0, mode="clip").astype(jnp.float32)
    c1 = jnp.take(codes, a + 1, axis=0, mode="clip").astype(jnp.float32)

    r = zero + ramp * i  # dequantize-on-read: chord ramp + scaled code
    y0 = r + scale * c0
    y1 = (r + ramp) + scale * c1

    t = u - i
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
    o_ref[...] = (y0 + t * (y1 - y0)).astype(o_ref.dtype)


def _quant_grad_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                       scale_ref, zero_ref, ramp_ref, codes_ref, y_ref, dy_ref,
                       *, bo: int, lo: int, n_intervals: int,
                       extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)
    p, invd, base, segs, scale, zero, ramp = _quant_select(
        x, bounds_ref, invd_ref, base_ref, segs_ref, scale_ref, zero_ref,
        ramp_ref, bo=bo, lo=lo, n=n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    codes = codes_ref[0, :]
    c0 = jnp.take(codes, a, axis=0, mode="clip").astype(jnp.float32)
    c1 = jnp.take(codes, a + 1, axis=0, mode="clip").astype(jnp.float32)

    r = zero + ramp * i
    y0 = r + scale * c0
    y1 = (r + ramp) + scale * c1

    t = u - i
    slope = (ramp + scale * (c1 - c0)) * invd
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
        inside = ((x >= bounds_ref[0, bo]) &
                  (x < bounds_ref[0, bo + n_intervals])).astype(jnp.float32)
        slope = slope * inside
    y_ref[...] = (y0 + t * (y1 - y0)).astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "bo", "lo",
                              "n_intervals", "extrapolate"))
def _quant_call(x2d, bounds, invd, base, segs, scale, zero, ramp, codes, *,
                block_rows, interpret, bo, lo, n_intervals, extrapolate):
    operands = (bounds, invd, base, segs, scale, zero, ramp, codes)
    grid, in_specs = _pack_specs(x2d, operands, block_rows)
    kernel = functools.partial(_quant_kernel, bo=bo, lo=lo,
                               n_intervals=n_intervals, extrapolate=extrapolate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, *operands)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "bo", "lo",
                              "n_intervals", "extrapolate"))
def _quant_call_grad(x2d, bounds, invd, base, segs, scale, zero, ramp, codes,
                     *, block_rows, interpret, bo, lo, n_intervals,
                     extrapolate):
    operands = (bounds, invd, base, segs, scale, zero, ramp, codes)
    grid, in_specs = _pack_specs(x2d, operands, block_rows)
    kernel = functools.partial(_quant_grad_kernel, bo=bo, lo=lo,
                               n_intervals=n_intervals, extrapolate=extrapolate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)] * 2,
        interpret=interpret,
    )(x2d, *operands)


def _quant_operands(pack: QuantTablePack, fid: int):
    return (pack.boundaries.reshape(1, -1), pack.inv_delta.reshape(1, -1),
            pack.base.reshape(1, -1), pack.seg_count.reshape(1, -1),
            pack.scale.reshape(1, -1), pack.zero.reshape(1, -1),
            pack.ramp.reshape(1, -1), pack.codes_for(fid).reshape(1, -1))


def quant_pack_lookup_pallas(
    pack: QuantTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Evaluate member ``fn`` from the quantized pack (dequantize-on-read)."""
    fid, x2d, block, n, interpret = _prep(pack, fn, x, lane, block_rows,
                                          interpret)
    out = _quant_call(
        x2d, *_quant_operands(pack, fid),
        block_rows=block, interpret=interpret, bo=pack.bounds_offset(fid),
        lo=pack.lane_offset(fid), n_intervals=pack.n_intervals[fid],
        extrapolate=extrapolate,
    )
    return untile_activations(out, n, x.shape)


def quant_pack_grad_pallas(
    pack: QuantTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
):
    """Returns (y, dy/dx) from the quantized pack in one fused selector pass."""
    fid, x2d, block, n, interpret = _prep(pack, fn, x, lane, block_rows,
                                          interpret)
    y2d, dy2d = _quant_call_grad(
        x2d, *_quant_operands(pack, fid),
        block_rows=block, interpret=interpret, bo=pack.bounds_offset(fid),
        lo=pack.lane_offset(fid), n_intervals=pack.n_intervals[fid],
        extrapolate=extrapolate,
    )
    return (untile_activations(y2d, n, x.shape),
            untile_activations(dy2d, n, x.shape))


def table_pack_grad_pallas(
    pack: TablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
):
    """Returns (y, dy/dx) for member ``fn`` with one fused selector pass."""
    fid, x2d, block, n, interpret = _prep(pack, fn, x, lane, block_rows,
                                          interpret)
    y2d, dy2d = _call_grad(
        x2d, pack.boundaries, pack.inv_delta, pack.base, pack.seg_count,
        pack.values,
        block_rows=block, interpret=interpret, fn_id=fid,
        n_intervals=pack.n_intervals[fid], span=member_span(pack, fid),
        extrapolate=extrapolate,
    )
    return (untile_activations(y2d, n, x.shape),
            untile_activations(dy2d, n, x.shape))


# --------------------------------------------------------------------------------------
# PolyPack kernels — degree-d coefficient codes VMEM-resident, dequant + Horner on read.
# --------------------------------------------------------------------------------------
#
# The polynomial pack generalizes the quant kernel from 2 chord endpoints to
# ``degree + 1`` monomial coefficients per cell: each lane is gathered from the
# member's width group (int8 / int16 codes or raw f32 coefficients — the f32
# members ride the SAME dequant FMA with zero = ramp = 0, scale = 1, a bit-exact
# identity) at ``base + i*(degree+1) + l``, dequantized per lane, and combined
# by Horner at the clamped cell coordinate.  ``extrapolate=True`` continues past
# the grid along the tangent: ``y = p(tc) + p'(tc) * (t - tc)``.  The dequant
# planes are lane-padded flat lanes (stride ``lmax = max_degree + 1``); the
# static fid bakes the member's degree, so only its real lanes are touched here
# (the routed kernel runs all lmax lanes — identical bits, see
# ``repro.core.packing.PolyPackLayout``).


def _poly_select(x, bounds_ref, invd_ref, base_ref, segs_ref, *, bo: int,
                 lo: int, n: int):
    """Comparator plane + four selector gathers from member (bo, lo, n)."""
    brow = bounds_ref[0, bo : bo + n + 1]
    j = select_interval(brow, n, x)
    p = jnp.take(brow, j, axis=0, mode="clip")
    invd = jnp.take(invd_ref[0, lo : lo + n], j, axis=0, mode="clip")
    base = jnp.take(base_ref[0, lo : lo + n], j, axis=0, mode="clip")
    segs = jnp.take(segs_ref[0, lo : lo + n], j, axis=0, mode="clip")
    return j, p, invd, base, segs


def _poly_coeffs_kernel(j, i, base, zero_ref, ramp_ref, scale_ref, codes_ref,
                        *, lo: int, n: int, lmax: int, degree: int):
    """Gather + dequantize the cell's ``degree + 1`` coefficient lanes."""
    codes = codes_ref[0, :]
    stride = float(degree + 1)
    cs = []
    for l in range(degree + 1):
        m = j * lmax + l  # flat (sub-interval, lane) metadata index
        zl = jnp.take(zero_ref[0, lo * lmax : (lo + n) * lmax], m, axis=0,
                      mode="clip")
        rl = jnp.take(ramp_ref[0, lo * lmax : (lo + n) * lmax], m, axis=0,
                      mode="clip")
        sl = jnp.take(scale_ref[0, lo * lmax : (lo + n) * lmax], m, axis=0,
                      mode="clip")
        a = (base + i * stride + float(l)).astype(jnp.int32)
        q = jnp.take(codes, a, axis=0, mode="clip").astype(jnp.float32)
        cs.append((zl + rl * i) + sl * q)
    return cs


def _poly_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref, zero_ref,
                 ramp_ref, scale_ref, codes_ref, o_ref, *, bo: int, lo: int,
                 n_intervals: int, lmax: int, degree: int, extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)
    j, p, invd, base, segs = _poly_select(
        x, bounds_ref, invd_ref, base_ref, segs_ref, bo=bo, lo=lo,
        n=n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    cs = _poly_coeffs_kernel(j, i, base, zero_ref, ramp_ref, scale_ref,
                             codes_ref, lo=lo, n=n_intervals, lmax=lmax,
                             degree=degree)
    t = u - i
    tc = jnp.clip(t, 0.0, 1.0)
    y = poly_horner(cs, tc)
    if extrapolate:
        y = y + poly_horner_d1(cs, tc) * (t - tc)
    o_ref[...] = y.astype(o_ref.dtype)


def _poly_grad_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                      zero_ref, ramp_ref, scale_ref, codes_ref, y_ref, dy_ref,
                      *, bo: int, lo: int, n_intervals: int, lmax: int,
                      degree: int, extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)
    j, p, invd, base, segs = _poly_select(
        x, bounds_ref, invd_ref, base_ref, segs_ref, bo=bo, lo=lo,
        n=n_intervals)

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    cs = _poly_coeffs_kernel(j, i, base, zero_ref, ramp_ref, scale_ref,
                             codes_ref, lo=lo, n=n_intervals, lmax=lmax,
                             degree=degree)
    t = u - i
    tc = jnp.clip(t, 0.0, 1.0)
    y = poly_horner(cs, tc)
    g = poly_horner_d1(cs, tc)
    slope = g * invd
    if extrapolate:
        y = y + g * (t - tc)
    else:
        inside = ((x >= bounds_ref[0, bo]) &
                  (x < bounds_ref[0, bo + n_intervals])).astype(jnp.float32)
        slope = slope * inside
    y_ref[...] = y.astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "bo", "lo",
                              "n_intervals", "lmax", "degree", "extrapolate"))
def _poly_call(x2d, bounds, invd, base, segs, zero, ramp, scale, codes, *,
               block_rows, interpret, bo, lo, n_intervals, lmax, degree,
               extrapolate):
    operands = (bounds, invd, base, segs, zero, ramp, scale, codes)
    grid, in_specs = _pack_specs(x2d, operands, block_rows)
    kernel = functools.partial(_poly_kernel, bo=bo, lo=lo,
                               n_intervals=n_intervals, lmax=lmax,
                               degree=degree, extrapolate=extrapolate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, *operands)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "bo", "lo",
                              "n_intervals", "lmax", "degree", "extrapolate"))
def _poly_call_grad(x2d, bounds, invd, base, segs, zero, ramp, scale, codes,
                    *, block_rows, interpret, bo, lo, n_intervals, lmax,
                    degree, extrapolate):
    operands = (bounds, invd, base, segs, zero, ramp, scale, codes)
    grid, in_specs = _pack_specs(x2d, operands, block_rows)
    kernel = functools.partial(_poly_grad_kernel, bo=bo, lo=lo,
                               n_intervals=n_intervals, lmax=lmax,
                               degree=degree, extrapolate=extrapolate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)] * 2,
        interpret=interpret,
    )(x2d, *operands)


def _poly_operands(pack: PolyTablePack, fid: int):
    return (pack.boundaries.reshape(1, -1), pack.inv_delta.reshape(1, -1),
            pack.base.reshape(1, -1), pack.seg_count.reshape(1, -1),
            pack.zero.reshape(1, -1), pack.ramp.reshape(1, -1),
            pack.scale.reshape(1, -1), pack.codes_for(fid).reshape(1, -1))


def poly_pack_lookup_pallas(
    pack: PolyTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Evaluate member ``fn`` from the polynomial pack (dequant + Horner)."""
    fid, x2d, block, n, interpret = _prep(pack, fn, x, lane, block_rows,
                                          interpret)
    out = _poly_call(
        x2d, *_poly_operands(pack, fid),
        block_rows=block, interpret=interpret, bo=pack.bounds_offset(fid),
        lo=pack.lane_offset(fid), n_intervals=pack.n_intervals[fid],
        lmax=pack.max_lanes, degree=pack.degrees[fid], extrapolate=extrapolate,
    )
    return untile_activations(out, n, x.shape)


def poly_pack_grad_pallas(
    pack: PolyTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
):
    """Returns (y, dy/dx) from the polynomial pack in one fused selector pass."""
    fid, x2d, block, n, interpret = _prep(pack, fn, x, lane, block_rows,
                                          interpret)
    y2d, dy2d = _poly_call_grad(
        x2d, *_poly_operands(pack, fid),
        block_rows=block, interpret=interpret, bo=pack.bounds_offset(fid),
        lo=pack.lane_offset(fid), n_intervals=pack.n_intervals[fid],
        lmax=pack.max_lanes, degree=pack.degrees[fid], extrapolate=extrapolate,
    )
    return (untile_activations(y2d, n, x.shape),
            untile_activations(dy2d, n, x.shape))


# --------------------------------------------------------------------------------------
# ShardedPack kernels — one shard's values slice VMEM-resident, unowned rows masked.
# --------------------------------------------------------------------------------------
#
# The replicated kernels above pin the WHOLE values vector; the sharded kernel
# pins one shard's padded slice plus the (small, replicated) selector metadata
# and the shard's (local_base, owned) planes.  The body is the static pack
# body with two changes: the base gather reads the SHARD-LOCAL rebased
# address, and the output is masked to the sub-intervals this shard owns.
# Contributions combine OUTSIDE the kernel — a psum over the mesh 'model'
# axis under shard_map, or a stacked-axis sum off-mesh — adding one owner
# value and S-1 zeros, so the summed result is bit-identical to the
# replicated kernel (asserted in tests/test_sharded_pack.py and the
# conformance matrix).


def _spack_kernel(x_ref, bounds_ref, invd_ref, segs_ref, lbase_ref, own_ref,
                  values_ref, o_ref, *, fn_id: int, n_intervals: int,
                  extrapolate: bool, slope: bool):
    x = x_ref[...].astype(jnp.float32)

    brow = bounds_ref[fn_id, :]
    j = select_interval(brow, n_intervals, x)
    p = jnp.take(brow, j, axis=0, mode="clip")
    invd = jnp.take(invd_ref[fn_id, :], j, axis=0, mode="clip")
    segs = jnp.take(segs_ref[fn_id, :], j, axis=0, mode="clip")
    base = jnp.take(lbase_ref[fn_id, :], j, axis=0, mode="clip")
    own = jnp.take(own_ref[fn_id, :], j, axis=0, mode="clip")

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)  # SHARD-LOCAL address (rebased at plan time)

    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    if slope:
        out = (y1 - y0) * invd
        if not extrapolate:
            inside = ((x >= brow[0]) & (x < brow[n_intervals]))
            out = out * inside.astype(jnp.float32)
    else:
        t = u - i
        if not extrapolate:
            t = jnp.clip(t, 0.0, 1.0)
        out = y0 + t * (y1 - y0)
    o_ref[...] = jnp.where(own > 0, out, 0.0).astype(o_ref.dtype)


def _spack_grad_kernel(x_ref, bounds_ref, invd_ref, segs_ref, lbase_ref,
                       own_ref, values_ref, y_ref, dy_ref, *, fn_id: int,
                       n_intervals: int, extrapolate: bool):
    """Fused (value, slope) shard contribution in ONE selector pass — the
    sharded twin of ``_pack_grad_kernel`` (same ops, masked outputs)."""
    x = x_ref[...].astype(jnp.float32)

    brow = bounds_ref[fn_id, :]
    j = select_interval(brow, n_intervals, x)
    p = jnp.take(brow, j, axis=0, mode="clip")
    invd = jnp.take(invd_ref[fn_id, :], j, axis=0, mode="clip")
    segs = jnp.take(segs_ref[fn_id, :], j, axis=0, mode="clip")
    base = jnp.take(lbase_ref[fn_id, :], j, axis=0, mode="clip")
    own = jnp.take(own_ref[fn_id, :], j, axis=0, mode="clip")

    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    t = u - i
    slope = (y1 - y0) * invd
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
        inside = ((x >= brow[0]) &
                  (x < brow[n_intervals])).astype(jnp.float32)
        slope = slope * inside
    y_ref[...] = jnp.where(own > 0, y0 + t * (y1 - y0), 0.0).astype(y_ref.dtype)
    dy_ref[...] = jnp.where(own > 0, slope, 0.0).astype(dy_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "fn_id", "n_intervals",
                              "extrapolate", "slope"))
def _sharded_call(x2d, bounds, invd, segs, lbase, own, values, *, block_rows,
                  interpret, fn_id, n_intervals, extrapolate, slope):
    grid, in_specs = _pack_specs(x2d, (bounds, invd, segs, lbase, own, values),
                                 block_rows)
    kernel = functools.partial(_spack_kernel, fn_id=fn_id,
                               n_intervals=n_intervals, extrapolate=extrapolate,
                               slope=slope)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, bounds, invd, segs, lbase, own, values)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "fn_id", "n_intervals",
                              "extrapolate"))
def _sharded_call_grad(x2d, bounds, invd, segs, lbase, own, values, *,
                       block_rows, interpret, fn_id, n_intervals, extrapolate):
    grid, in_specs = _pack_specs(x2d, (bounds, invd, segs, lbase, own, values),
                                 block_rows)
    kernel = functools.partial(_spack_grad_kernel, fn_id=fn_id,
                               n_intervals=n_intervals, extrapolate=extrapolate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)] * 2,
        interpret=interpret,
    )(x2d, bounds, invd, segs, lbase, own, values)


def sharded_shard_contrib_pallas(
    boundaries: jax.Array,
    inv_delta: jax.Array,
    seg_count: jax.Array,
    local_base: jax.Array,
    owned: jax.Array,
    values_s: jax.Array,
    x: jax.Array,
    *,
    fn_id: int,
    n_intervals: int,
    extrapolate: bool = False,
    slope: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """ONE shard's masked contribution from explicit (mesh-local) arrays.

    This is the entry the shard_map body calls: ``local_base``/``owned`` are
    the (F, n_max) planes of the CALLING shard and ``values_s`` its (m_max,)
    slice.  The caller combines contributions (psum on mesh, sum off-mesh).
    """
    interpret = resolve_interpret(interpret)
    x2d, block, n = tile_activations(x, lane, block_rows)
    out = _sharded_call(
        x2d, boundaries, inv_delta, seg_count, local_base, owned,
        values_s.reshape(1, -1),
        block_rows=block, interpret=interpret, fn_id=fn_id,
        n_intervals=n_intervals, extrapolate=extrapolate, slope=slope)
    return untile_activations(out, n, x.shape)


def _sharded_sum_pallas(pack: ShardedTablePack, fn, x, extrapolate, slope,
                        block_rows, lane, interpret):
    fid = pack.member_id(fn)
    out = None
    for s in range(pack.n_shards):
        c = sharded_shard_contrib_pallas(
            pack.boundaries, pack.inv_delta, pack.seg_count,
            pack.local_base[s], pack.owned[s], pack.values[s], x,
            fn_id=fid, n_intervals=pack.n_intervals[fid],
            extrapolate=extrapolate, slope=slope, block_rows=block_rows,
            lane=lane, interpret=interpret)
        out = c if out is None else out + c
    return out


def sharded_pack_lookup_pallas(
    pack: ShardedTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Evaluate member ``fn`` of the sharded pack (stacked shard axis: one
    kernel launch per shard, contributions summed — the off-mesh path)."""
    return _sharded_sum_pallas(pack, fn, x, extrapolate, False, block_rows,
                               lane, interpret)


def sharded_pack_slope_pallas(
    pack: ShardedTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Slope only (no value pass) — bit-identical to ``eval_sharded_slope``."""
    return _sharded_sum_pallas(pack, fn, x, extrapolate, True, block_rows,
                               lane, interpret)


def sharded_pack_grad_pallas(
    pack: ShardedTablePack,
    fn,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
):
    """Returns (y, dy/dx) from the sharded pack — one FUSED selector pass per
    shard (S launches total, like the replicated ``table_pack_grad_pallas``'s
    single fused launch)."""
    interpret = resolve_interpret(interpret)
    fid = pack.member_id(fn)
    x2d, block, n = tile_activations(x, lane, block_rows)
    y2d = dy2d = None
    for s in range(pack.n_shards):
        cy, cdy = _sharded_call_grad(
            x2d, pack.boundaries, pack.inv_delta, pack.seg_count,
            pack.local_base[s], pack.owned[s], pack.values[s].reshape(1, -1),
            block_rows=block, interpret=interpret, fn_id=fid,
            n_intervals=pack.n_intervals[fid], extrapolate=extrapolate)
        y2d = cy if y2d is None else y2d + cy
        dy2d = cdy if dy2d is None else dy2d + cdy
    return (untile_activations(y2d, n, x.shape),
            untile_activations(dy2d, n, x.shape))


# --------------------------------------------------------------------------------------
# RangeFold kernels — fold prologue + core lookup(s) + reconstruction epilogue,
# all fused in ONE kernel body (mode="folded_pack").
# --------------------------------------------------------------------------------------
#
# The reduction (repro.core.range_reduce) folds the unbounded argument onto the
# canonical core interval INSIDE the kernel — Cody-Waite / Payne-Hanek for trig,
# exponent-field splits for exp/log — then the standard comparator-plane lookup
# reads the core member(s) and the epilogue reapplies the exact bookkeeping
# (octant sign/swap, 2^k scaling, e*ln2 shift).  Trig needs TWO static-fn_id
# core reads per element (sin_core and cos_core feed the quadrant select); exp
# and log need one.  Because the fold helpers are the same jnp functions the
# oracle (repro.approx.range_fold.eval_folded_ref) calls, the kernel/oracle pair
# is bit-identical by construction, like select_interval before it.


def _folded_core_lookup(x, bounds_ref, invd_ref, base_ref, segs_ref, values,
                        fid: int, n_intervals: int):
    """One core-member read: identical op sequence to ``eval_pack_ref`` with
    ``extrapolate=False`` (the cores never extrapolate — the fold guarantees
    in-domain arguments up to the guard band, which clamps)."""
    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, fid, n_intervals)
    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")
    t = jnp.clip(u - i, 0.0, 1.0)
    return y0 + t * (y1 - y0)


def _folded_core_slope(x, bounds_ref, invd_ref, base_ref, segs_ref, values,
                       fid: int, n_intervals: int):
    """Chord slope of one core member — mirrors ``eval_pack_slope``."""
    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, fid, n_intervals)
    i = jnp.clip(jnp.floor((x - p) * invd), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")
    slope = (y1 - y0) * invd
    inside = (x >= bounds_ref[fid, 0]) & (x < bounds_ref[fid, n_intervals])
    return slope * inside.astype(jnp.float32)


def _folded_value(x, bounds_ref, invd_ref, base_ref, segs_ref, values, *,
                  kind: str, fid_a: int, fid_b: int, n_a: int, n_b: int):
    look = lambda v, fid, n: _folded_core_lookup(
        v, bounds_ref, invd_ref, base_ref, segs_ref, values, fid, n)
    if kind in ("sin", "cos"):
        r, q, sflip = trig_fold(x)
        y = trig_reconstruct(kind, look(r, fid_a, n_a), look(r, fid_b, n_b),
                             q, sflip)
        return trig_edges(x, y)
    if kind == "exp":
        r, k = exp_fold(x)
        return exp_edges(x, exp_reconstruct(look(r, fid_a, n_a), k))
    m, e = log_fold(x)
    return log_edges(x, log_reconstruct(look(m, fid_a, n_a), e))


def _folded_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref, values_ref,
                   o_ref, *, kind: str, fid_a: int, fid_b: int, n_a: int,
                   n_b: int):
    x = x_ref[...].astype(jnp.float32)
    y = _folded_value(x, bounds_ref, invd_ref, base_ref, segs_ref,
                      values_ref[0, :], kind=kind, fid_a=fid_a, fid_b=fid_b,
                      n_a=n_a, n_b=n_b)
    o_ref[...] = y.astype(o_ref.dtype)


def _folded_grad_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                        values_ref, y_ref, dy_ref, *, kind: str, fid_a: int,
                        fid_b: int, n_a: int, n_b: int):
    from repro.approx.range_fold import _log_slope_mask, _log_slope_safe_x

    x = x_ref[...].astype(jnp.float32)
    values = values_ref[0, :]
    y = _folded_value(x, bounds_ref, invd_ref, base_ref, segs_ref, values,
                      kind=kind, fid_a=fid_a, fid_b=fid_b, n_a=n_a, n_b=n_b)
    sl = lambda v, fid, n: _folded_core_slope(
        v, bounds_ref, invd_ref, base_ref, segs_ref, values, fid, n)
    if kind in ("sin", "cos"):
        r, q, sflip = trig_fold(x)
        slope = trig_slope_reconstruct(kind, sl(r, fid_a, n_a),
                                       sl(r, fid_b, n_b), q, sflip)
        slope = jnp.where(jnp.isfinite(x), slope, 0.0)
    elif kind == "exp":
        r, k = exp_fold(x)
        slope = exp_reconstruct(sl(r, fid_a, n_a), k)
        # zero overflowed-2^k lanes too (matches eval_folded_slope)
        slope = jnp.where(jnp.isfinite(x) & jnp.isfinite(slope), slope, 0.0)
    else:
        m, e = log_fold(x)
        slope = _log_slope_mask(x) * sl(m, fid_a, n_a) \
            * (m / _log_slope_safe_x(x))
    y_ref[...] = y.astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "kind", "fid_a",
                              "fid_b", "n_a", "n_b"))
def _folded_call(x2d, bounds, invd, base, segs, values, *, block_rows,
                 interpret, kind, fid_a, fid_b, n_a, n_b):
    grid, in_specs = _pack_specs(x2d, (bounds, invd, base, segs, values),
                                 block_rows)
    kernel = functools.partial(_folded_kernel, kind=kind, fid_a=fid_a,
                               fid_b=fid_b, n_a=n_a, n_b=n_b)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, bounds, invd, base, segs, values)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "kind", "fid_a",
                              "fid_b", "n_a", "n_b"))
def _folded_call_grad(x2d, bounds, invd, base, segs, values, *, block_rows,
                      interpret, kind, fid_a, fid_b, n_a, n_b):
    grid, in_specs = _pack_specs(x2d, (bounds, invd, base, segs, values),
                                 block_rows)
    kernel = functools.partial(_folded_grad_kernel, kind=kind, fid_a=fid_a,
                               fid_b=fid_b, n_a=n_a, n_b=n_b)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_rows, x2d.shape[1]), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)] * 2,
        interpret=interpret,
    )(x2d, bounds, invd, base, segs, values)


def _folded_prep(pack: TablePack, name: str, x, lane, block_rows, interpret):
    from repro.approx.range_fold import FOLDABLE

    interpret = resolve_interpret(interpret)
    if name not in FOLDABLE:
        raise KeyError(f"folded kernel serves {sorted(FOLDABLE)}, got {name!r}; "
                       f"use table_pack_lookup_pallas for plain members")
    cores = FOLDABLE[name]
    fid_a = pack.member_id(cores[0])
    fid_b = pack.member_id(cores[1]) if len(cores) > 1 else fid_a
    x2d, block, n = tile_activations(x, lane, block_rows)
    return fid_a, fid_b, x2d, block, n, interpret


def folded_pack_lookup_pallas(
    pack: TablePack,
    name: str,
    x: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Full-f32-range ``sin``/``cos``/``exp``/``log`` over a tensor: fold +
    core lookup(s) + reconstruction fused in one kernel launch."""
    fid_a, fid_b, x2d, block, n, interpret = _folded_prep(
        pack, name, x, lane, block_rows, interpret)
    out = _folded_call(
        x2d, pack.boundaries, pack.inv_delta, pack.base, pack.seg_count,
        pack.values.reshape(1, -1),
        block_rows=block, interpret=interpret, kind=name, fid_a=fid_a,
        fid_b=fid_b, n_a=pack.n_intervals[fid_a], n_b=pack.n_intervals[fid_b])
    return untile_activations(out, n, x.shape)


def folded_pack_grad_pallas(
    pack: TablePack,
    name: str,
    x: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
):
    """Fused (y, dy/dx) of the folded surrogate in one selector pass."""
    fid_a, fid_b, x2d, block, n, interpret = _folded_prep(
        pack, name, x, lane, block_rows, interpret)
    y2d, dy2d = _folded_call_grad(
        x2d, pack.boundaries, pack.inv_delta, pack.base, pack.seg_count,
        pack.values.reshape(1, -1),
        block_rows=block, interpret=interpret, kind=name, fid_a=fid_a,
        fid_b=fid_b, n_a=pack.n_intervals[fid_a], n_b=pack.n_intervals[fid_b])
    return untile_activations(y2d, n, x.shape), untile_activations(dy2d, n, x.shape)
