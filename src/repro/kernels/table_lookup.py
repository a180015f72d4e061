"""Pallas TPU kernel: fused interval-select + table-lookup + linear interpolation.

This is the paper's Fig. 7 pipeline re-thought for the TPU memory hierarchy
(DESIGN.md §2):

  * the packed table (``values``) and selector metadata are **VMEM-resident for the
    whole kernel** — the BRAM analogue.  BlockSpecs pin them with a constant index
    map so every grid step reuses the same VMEM copy; only activation tiles stream
    HBM→VMEM.
  * the interval selector is a *comparator plane*: per boundary, one vector
    ``x >= b_m`` compare selects that sub-interval's four parameters
    (``select_params``; scalar metadata reads, which Mosaic takes from SMEM).
    The paper's binary comparator tree (and its LUT-count versus #intervals
    tradeoff, Fig. 8b) has no TPU meaning — a VPU evaluates a comparator over a
    whole tile at once, and selects pick stored values exactly, so there is
    no accumulated rounding.
  * address generation uses precomputed reciprocals ``inv_delta`` (no divide on the
    VPU hot path) and float accumulators (exact for indices < 2^24).
  * the dual-port BRAM read of (y_i, y_{i+1}) becomes one adjacent-pair gather from
    the VMEM table; the 5-cycle fixed-point lerp becomes a single FMA.  This
    per-table kernel gathers from a 1-D VMEM row, which the TPU compiler refuses;
    the ``table_pack`` kernels read a (rows, 128) values plane instead.

Tile geometry: activations are flattened to (rows, LANE) with LANE a multiple of 128
(the VREG lane width) and rows blocked at ``block_rows`` (a multiple of 8 sublanes),
so each tile is MXU/VPU aligned.  VMEM working set per grid step:
``block_rows*LANE*4 (in) + same (out) + table bytes`` — checked against the VMEM
budget by ``repro.core.bram.vmem_cost``.

Validated against ``ref.table_lookup_ref`` in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.approx.jax_table import JaxTable

LANE = 512  # 4 VREG lanes worth of f32; amortizes control per vector op
DEFAULT_BLOCK_ROWS = 256  # 256x512 f32 tile = 512 KiB in + 512 KiB out


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a table kernel runs in Pallas interpret mode.

    An explicit ``interpret`` wins (tests steer it).  Otherwise kernels are
    interpreted on CPU and compiled on TPU; any other backend is an error, so
    no accelerator ever runs the interpreter loop without saying so.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas table kernels run compiled on TPU or "
                       f"interpreted on CPU; backend {backend!r} is neither")


def select_params(x, bounds_ref, invd_ref, base_ref, segs_ref, row: int,
                  n_intervals: int):
    """Comparator plane + parameter fetch, shared by every table kernel.

    A running compare-and-select over metadata row ``row`` of the four refs:
    for each m in 1..n-1, ``x >= b_m`` overwrites the four parameters with
    entry m.  Boundaries ascend, so the last hit is ``select_interval``'s
    ``clip(#(x >= b_m), 0, n-1)`` and the selects return exactly the entries
    the jnp oracles gather (bit-identical; NaN selects entry 0 in both).
    Every read is a static scalar ``ref[row, m]``, which Mosaic lowers from
    SMEM.  Right-padding past ``n_intervals`` (+inf in the pack plane) is
    never read.
    """
    read = lambda m: tuple(r[row, m] for r in (bounds_ref, invd_ref, base_ref,
                                                segs_ref))
    p, invd, base, segs = (jnp.broadcast_to(v, x.shape) for v in read(0))
    for m in range(1, n_intervals):
        b, d, o, s = read(m)
        hit = x >= b
        p = jnp.where(hit, b, p)
        invd = jnp.where(hit, d, invd)
        base = jnp.where(hit, o, base)
        segs = jnp.where(hit, s, segs)
    return p, invd, base, segs


def _table_kernel(x_ref, bounds_ref, invd_ref, base_ref, segs_ref, values_ref, o_ref,
                  *, n_intervals: int, extrapolate: bool):
    x = x_ref[...].astype(jnp.float32)

    # --- interval selector + parameter fetch (comparator plane + gathers) -------
    p, invd, base, segs = select_params(
        x, bounds_ref, invd_ref, base_ref, segs_ref, 0, n_intervals)

    # --- address generation (reciprocal multiply + floor + clamp) ---------------
    u = (x - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)

    # --- BRAM read: adjacent-pair gather from the VMEM-resident table -----------
    values = values_ref[0, :]
    y0 = jnp.take(values, a, axis=0, mode="clip")
    y1 = jnp.take(values, a + 1, axis=0, mode="clip")

    # --- linear interpolation (one FMA); edge handling: saturate (hardware clamp)
    # or extend the edge segments linearly (asymptote-correct for gelu/silu) -----
    t = u - i
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
    o_ref[...] = (y0 + t * (y1 - y0)).astype(o_ref.dtype)


def _pinned(shape):
    """BlockSpec that keeps a whole operand resident in VMEM across grid steps."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def tile_activations(x: jax.Array, lane: int, block_rows: int):
    """Flatten + zero-pad an arbitrary tensor into an MXU/VPU-aligned 2D tiling.

    Shared by every table kernel wrapper (per-table and pack) so the whole
    subsystem pads exactly one way.  Returns ``(x2d, block, n)`` with
    ``x2d: (rows_pad, lane)``, ``block`` the largest grid-dividing row block
    <= ``block_rows``, and ``n`` the true element count for untiling.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // lane)
    block = min(block_rows, rows)
    rows_pad = -(-rows // block) * block
    pad = rows_pad * lane - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows_pad, lane), block, n


def untile_activations(out2d: jax.Array, n: int, shape) -> jax.Array:
    """Inverse of :func:`tile_activations` for one kernel output."""
    return out2d.reshape(-1)[:n].reshape(shape)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret", "n_intervals", "extrapolate")
)
def _call(x2d, bounds, invd, base, segs, values, *, block_rows, interpret, n_intervals,
          extrapolate):
    rows, lane = x2d.shape
    grid = (rows // block_rows,)
    kernel = functools.partial(
        _table_kernel, n_intervals=n_intervals, extrapolate=extrapolate
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
            _pinned(bounds.shape),
            _pinned(invd.shape),
            _pinned(base.shape),
            _pinned(segs.shape),
            _pinned(values.shape),
        ],
        out_specs=pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d, bounds, invd, base, segs, values)


def table_lookup_pallas(
    jt: JaxTable,
    x: jax.Array,
    *,
    extrapolate: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    lane: int = LANE,
    interpret: bool | None = None,
) -> jax.Array:
    """Evaluate the table approximator over an arbitrarily-shaped tensor."""
    interpret = resolve_interpret(interpret)
    shape = x.shape
    x2d, block, n = tile_activations(x, lane, block_rows)
    out = _call(
        x2d,
        jt.boundaries.reshape(1, -1),
        jt.inv_delta.reshape(1, -1),
        jt.base.reshape(1, -1),
        jt.seg_count.reshape(1, -1),
        jt.values.reshape(1, -1),
        block_rows=block,
        interpret=interpret,
        n_intervals=jt.n_intervals,
        extrapolate=extrapolate,
    )
    return untile_activations(out, n, shape)
