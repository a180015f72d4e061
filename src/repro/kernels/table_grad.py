"""Fused forward+slope Pallas kernel: one interval-selection pass yields BOTH the
table value y(x) and the piecewise-linear derivative dy/dx.

The backward pass of a table activation needs the segment slope at x.  Running
the selector twice (forward kernel + slope kernel) doubles the comparator-plane
and gather work; this kernel shares them: after the (p, invd, base, segs) mux and
the adjacent-pair gather, the slope is one extra multiply
``(y1 - y0) * invd`` — the FPGA pipeline's subtract/multiply stage reused.

Used by ``repro.approx.make_table_fn`` when ``use_pallas=True``: the custom_jvp
calls this once instead of forward + slope separately.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.approx.jax_table import JaxTable

from .table_lookup import (DEFAULT_BLOCK_ROWS, LANE, _pinned, resolve_interpret,
                           select_params, tile_activations, untile_activations)


def _table_grad_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref,
                       values_ref, y_ref, dy_ref, *, n_intervals: int,
                       extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)

    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, 0, n_intervals)

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
        inside = ((x >= bounds_ref[0, 0]) &
                  (x < bounds_ref[0, n_intervals])).astype(jnp.float32)
        slope = slope * inside
    y_ref[...] = (y0 + t * (y1 - y0)).astype(y_ref.dtype)
    dy_ref[...] = slope.astype(dy_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "n_intervals",
                              "extrapolate"))
def _call(x2d, bounds, invd, base, segs, values, *, block_rows, interpret,
          n_intervals, extrapolate):
    rows, lane = x2d.shape
    kernel = functools.partial(_table_grad_kernel, n_intervals=n_intervals,
                               extrapolate=extrapolate)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
            _pinned(bounds.shape),
            _pinned(invd.shape),
            _pinned(base.shape),
            _pinned(segs.shape),
            _pinned(values.shape),
        ],
        out_specs=[pl.BlockSpec((block_rows, lane), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)] * 2,
        interpret=interpret,
    )(x2d, bounds, invd, base, segs, values)


def table_lookup_grad_pallas(
    jt: JaxTable,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
):
    """Returns (y, dy/dx) with one fused selector pass."""
    interpret = resolve_interpret(interpret)
    shape = x.shape
    x2d, block, n = tile_activations(x, lane, block_rows)
    y2d, dy2d = _call(
        x2d,
        jt.boundaries.reshape(1, -1),
        jt.inv_delta.reshape(1, -1),
        jt.base.reshape(1, -1),
        jt.seg_count.reshape(1, -1),
        jt.values.reshape(1, -1),
        block_rows=block, interpret=interpret,
        n_intervals=jt.n_intervals, extrapolate=extrapolate,
    )
    return (untile_activations(y2d, n, shape),
            untile_activations(dy2d, n, shape))
