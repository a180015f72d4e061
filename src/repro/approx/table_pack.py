"""TablePack — every table a model needs, fused into ONE device artifact.

The paper keeps each function's table resident in BRAM next to its consumer
(Sec. 7.2); a network, however, evaluates a *set* of nonlinearities (gelu for
the MLP, sigmoid/tanh for gates, exp for softmax...), and shipping one table +
one kernel dispatch per function multiplies both the VMEM residency and the
dispatch overhead by F.  A :class:`TablePack` concatenates all range values
into a single ``values`` vector and stores selector metadata as (F, n_max)
padded planes (see :class:`repro.core.packing.PackLayout`), so

  * ONE artifact stays VMEM-resident for the whole network (BRAM instantiation
    lifted to the function-set level), and
  * ONE fused Pallas kernel — ``repro.kernels.table_pack_lookup`` — serves any
    member function via a static ``fn_id`` row index.

``eval_pack_ref`` is the pure-jnp oracle; it reproduces the per-table
``eval_table_ref`` BIT FOR BIT (same compare/gather/FMA sequence on the same
f32 values; the pack only rebases the BRAM addresses), which the parity tests
assert for every registered function.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flow import cached_table
from repro.core.packing import (PackLayout, PolyPackLayout, QuantPackLayout,
                                ShardedPackLayout, pack_layout,
                                poly_pack_layout, quant_pack_layout,
                                shard_pack_layout)
from repro.core.quantize import plan_quant_member
from repro.core.table import TableSpec

from .jax_table import select_interval


def _member_id(names: Tuple[str, ...], fn) -> int:
    """Resolve a name or integer fn_id to a VALIDATED member index.

    Both unknown names and out-of-range integers raise ``KeyError`` naming the
    offender and listing the registered members — the raw tuple-index
    ``IndexError`` this replaces said neither.
    """
    if isinstance(fn, str):
        try:
            return names.index(fn)
        except ValueError:
            raise KeyError(f"function {fn!r} not in pack {names}") from None
    fid = int(fn)
    if not 0 <= fid < len(names):
        raise KeyError(
            f"fn_id {fid} out of range for pack with {len(names)} members "
            f"{names}") from None
    return fid


class TablePack(NamedTuple):
    """Device-ready multi-function table artifact (all array leaves jnp, f32)."""

    names: Tuple[str, ...]  # static: member function names (fn_id order)
    n_intervals: Tuple[int, ...]  # static: real sub-interval count per member
    boundaries: jax.Array  # (F, n_max+1) f32, right-padded +inf
    inv_delta: jax.Array  # (F, n_max)   f32
    delta: jax.Array  # (F, n_max)   f32
    base: jax.Array  # (F, n_max)   f32 — GLOBAL packed-values index (exact < 2^24)
    seg_count: jax.Array  # (F, n_max)   f32
    values: jax.Array  # (M,)         f32 — all member tables, concatenated

    @property
    def n_functions(self) -> int:
        return len(self.names)

    @property
    def n_max(self) -> int:
        return self.inv_delta.shape[1]

    @property
    def footprint(self) -> int:
        return self.values.shape[0]

    def fn_id(self, name: str) -> int:
        return _member_id(self.names, name)

    def member_id(self, fn) -> int:
        """Name or integer fn_id -> validated index (KeyError otherwise)."""
        return _member_id(self.names, fn)

    def routing_scalars(self) -> Tuple[np.ndarray, ...]:
        """Prefetched scalar operands for dynamic fn_id dispatch: ``(n_arr,)``
        with ``n_arr[f]`` the real sub-interval count of member ``f``."""
        return (np.asarray(self.n_intervals, dtype=np.int32),)


def from_layout(layout: PackLayout, dtype=jnp.float32) -> TablePack:
    if layout.footprint >= (1 << 24):
        raise ValueError("pack footprint exceeds f32 exact-integer range")
    return TablePack(
        names=layout.names,
        n_intervals=layout.n_intervals,
        boundaries=jnp.asarray(layout.boundaries, dtype=dtype),
        inv_delta=jnp.asarray(layout.inv_delta, dtype=dtype),
        delta=jnp.asarray(layout.delta, dtype=dtype),
        base=jnp.asarray(layout.base.astype(np.float64), dtype=dtype),
        seg_count=jnp.asarray(layout.seg_count.astype(np.float64), dtype=dtype),
        values=jnp.asarray(layout.values, dtype=dtype),
    )


def pack_specs(specs: Sequence[TableSpec]) -> TablePack:
    """Pack already-built TableSpecs (order defines fn_id)."""
    return from_layout(pack_layout(specs))


def build_pack(
    names: Sequence[str],
    e_a: float,
    *,
    algorithm: str = "hierarchical",
    omega: float = 0.3,
    intervals: Optional[dict] = None,
) -> TablePack:
    """Run the design flow for every name and fuse the artifacts into one pack."""
    intervals = intervals or {}
    specs = []
    for name in names:
        lo, hi = intervals.get(name, (None, None))
        specs.append(cached_table(name, e_a, lo, hi, algorithm=algorithm,
                                  omega=omega))
    return pack_specs(specs)


def _resolve(pack, fn) -> int:
    return pack.member_id(fn)


def _select_pack_params(pack: TablePack, fid: int, xf: jax.Array):
    """One selector + four gathers against function ``fid``'s metadata row."""
    brow = pack.boundaries[fid]
    j = select_interval(brow, pack.n_intervals[fid], xf)
    p = jnp.take(brow, j, axis=0)
    invd = jnp.take(pack.inv_delta[fid], j, axis=0)
    base = jnp.take(pack.base[fid], j, axis=0)
    segs = jnp.take(pack.seg_count[fid], j, axis=0)
    return p, invd, base, segs


def eval_pack_ref(pack: TablePack, fn, x: jax.Array, *,
                  extrapolate: bool = False) -> jax.Array:
    """Pure-jnp pack evaluation — bit-identical to per-table ``eval_table_ref``."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    p, invd, base, segs = _select_pack_params(pack, fid, xf)
    u = (xf - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    y0 = jnp.take(pack.values, a, axis=0)
    y1 = jnp.take(pack.values, a + 1, axis=0)
    t = u - i
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
    return (y0 + t * (y1 - y0)).astype(dtype)


def eval_pack_slope(pack: TablePack, fn, x: jax.Array, *,
                    extrapolate: bool = False) -> jax.Array:
    """d/dx of the pack surrogate — bit-identical to ``eval_table_slope``."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    p, invd, base, segs = _select_pack_params(pack, fid, xf)
    i = jnp.clip(jnp.floor((xf - p) * invd), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    y0 = jnp.take(pack.values, a, axis=0)
    y1 = jnp.take(pack.values, a + 1, axis=0)
    slope = (y1 - y0) * invd
    if not extrapolate:
        n = pack.n_intervals[fid]
        inside = (xf >= pack.boundaries[fid, 0]) & (xf < pack.boundaries[fid, n])
        slope = slope * inside.astype(jnp.float32)
    return slope.astype(dtype)


# --------------------------------------------------------------------------------------
# QuantPack — the pack with int8/int16 entry codes, dequantized on read.
# --------------------------------------------------------------------------------------


class QuantTablePack(NamedTuple):
    """Device-ready quantized multi-function pack.

    Entries live as int8/int16 codes in two width-group vectors; the selector
    metadata plus per-sub-interval dequant params (scale, zero, ramp) are flat
    RAGGED f32 lanes — member ``fid``'s segment starts at a STATIC offset
    derived from the static ``n_intervals`` tuple, so no (F, n_max) padding is
    paid (see :class:`repro.core.packing.QuantPackLayout`).  Dequantize-on-read
    is one extra FMA per gathered endpoint: ``v = (zero + ramp*i) + scale*q``.
    """

    names: Tuple[str, ...]  # static: member function names (fn_id order)
    n_intervals: Tuple[int, ...]  # static: sub-interval count per member
    entry_bits: Tuple[int, ...]  # static: 8 | 16 → which codes vector
    rho: Tuple[float, ...]  # static: interpolation share of e_a per member
    boundaries: jax.Array  # (sum n_f+1,) f32 flat rows
    inv_delta: jax.Array  # (sum n_f,) f32
    base: jax.Array  # (sum n_f,) f32 — GLOBAL index into the width-group codes
    seg_count: jax.Array  # (sum n_f,) f32
    scale: jax.Array  # (sum n_f,) f32
    zero: jax.Array  # (sum n_f,) f32
    ramp: jax.Array  # (sum n_f,) f32
    codes8: jax.Array  # (max(M8,1),) int8
    codes16: jax.Array  # (max(M16,1),) int16

    @property
    def n_functions(self) -> int:
        return len(self.names)

    @property
    def footprint(self) -> int:
        """Stored entries — excludes the 1-entry dummy of an unused width group,
        so it agrees with :class:`QuantPackLayout`'s accounting."""
        m8 = self.codes8.shape[0] if 8 in self.entry_bits else 0
        m16 = self.codes16.shape[0] if 16 in self.entry_bits else 0
        return int(m8 + m16)

    @property
    def footprint_bytes(self) -> int:
        m8 = self.codes8.shape[0] if 8 in self.entry_bits else 0
        m16 = self.codes16.shape[0] if 16 in self.entry_bits else 0
        return int(m8 + 2 * m16)

    def fn_id(self, name: str) -> int:
        return _member_id(self.names, name)

    def member_id(self, fn) -> int:
        """Name or integer fn_id -> validated index (KeyError otherwise)."""
        return _member_id(self.names, fn)

    def bounds_offset(self, fid: int) -> int:
        return sum(n + 1 for n in self.n_intervals[:fid])

    def lane_offset(self, fid: int) -> int:
        return sum(self.n_intervals[:fid])

    def codes_for(self, fid: int) -> jax.Array:
        return self.codes8 if self.entry_bits[fid] == 8 else self.codes16

    def routing_scalars(self) -> Tuple[np.ndarray, ...]:
        """Prefetched scalar operands for dynamic fn_id dispatch.

        The ragged static lane offsets (``bounds_offset`` / ``lane_offset``)
        and the per-member width-group choice, as int32 vectors a
        scalar-prefetch kernel indexes at runtime:
        ``(n_arr, bounds_offsets, lane_offsets, entry_bits)``.
        """
        F = self.n_functions
        return (np.asarray(self.n_intervals, dtype=np.int32),
                np.asarray([self.bounds_offset(f) for f in range(F)], np.int32),
                np.asarray([self.lane_offset(f) for f in range(F)], np.int32),
                np.asarray(self.entry_bits, dtype=np.int32))


def from_quant_layout(layout: QuantPackLayout) -> QuantTablePack:
    if max(len(layout.codes8), len(layout.codes16)) >= (1 << 24):
        raise ValueError("pack footprint exceeds f32 exact-integer range")

    def codes_arr(codes: np.ndarray, dtype) -> jax.Array:
        if len(codes) == 0:  # keep a 1-entry dummy so the operand stays valid
            return jnp.zeros((1,), dtype=dtype)
        return jnp.asarray(codes, dtype=dtype)

    f32 = lambda a: jnp.asarray(np.asarray(a, dtype=np.float64),
                                dtype=jnp.float32)
    return QuantTablePack(
        names=layout.names,
        n_intervals=layout.n_intervals,
        entry_bits=layout.entry_bits,
        rho=tuple(m.rho for m in layout.members),
        boundaries=f32(layout.boundaries),
        inv_delta=f32(layout.inv_delta),
        base=f32(layout.base),
        seg_count=f32(layout.seg_count),
        scale=f32(layout.scale),
        zero=f32(layout.zero),
        ramp=f32(layout.ramp),
        codes8=codes_arr(layout.codes8, jnp.int8),
        codes16=codes_arr(layout.codes16, jnp.int16),
    )


def build_quant_pack(
    names: Sequence[str],
    e_a: float,
    *,
    rho: float = 0.9,
    dtype: str = "auto",
    algorithm: str = "hierarchical",
    omega: float = 0.3,
    intervals: Optional[dict] = None,
) -> QuantTablePack:
    """Error-budgeted quantized pack: interpolation gets ``rho * e_a``, code
    rounding the rest; int8 vs int16 is chosen per member (``dtype='auto'``)."""
    intervals = intervals or {}
    members = []
    for name in names:
        lo, hi = intervals.get(name, (None, None))
        members.append(plan_quant_member(
            name, e_a, lo, hi, algorithm=algorithm, omega=omega,
            rho=rho, dtype=dtype))
    return from_quant_layout(quant_pack_layout(members))


def _quant_select(pack: QuantTablePack, fid: int, xf: jax.Array):
    """Selector + seven gathers against member ``fid``'s ragged lane segment."""
    bo, lo = pack.bounds_offset(fid), pack.lane_offset(fid)
    n = pack.n_intervals[fid]
    brow = pack.boundaries[bo : bo + n + 1]
    j = select_interval(brow, n, xf)
    p = jnp.take(brow, j, axis=0)
    invd = jnp.take(pack.inv_delta[lo : lo + n], j, axis=0)
    base = jnp.take(pack.base[lo : lo + n], j, axis=0)
    segs = jnp.take(pack.seg_count[lo : lo + n], j, axis=0)
    scale = jnp.take(pack.scale[lo : lo + n], j, axis=0)
    zero = jnp.take(pack.zero[lo : lo + n], j, axis=0)
    ramp = jnp.take(pack.ramp[lo : lo + n], j, axis=0)
    return p, invd, base, segs, scale, zero, ramp


def eval_quant_pack_ref(pack: QuantTablePack, fn, x: jax.Array, *,
                        extrapolate: bool = False) -> jax.Array:
    """Pure-jnp dequantize-on-read oracle — bit-identical to the Pallas kernel."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    p, invd, base, segs, scale, zero, ramp = _quant_select(pack, fid, xf)
    u = (xf - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    codes = pack.codes_for(fid)
    c0 = jnp.take(codes, a, axis=0).astype(jnp.float32)
    c1 = jnp.take(codes, a + 1, axis=0).astype(jnp.float32)
    r = zero + ramp * i  # the chord ramp at entry i
    y0 = r + scale * c0
    y1 = (r + ramp) + scale * c1
    t = u - i
    if not extrapolate:
        t = jnp.clip(t, 0.0, 1.0)
    return (y0 + t * (y1 - y0)).astype(dtype)


def eval_quant_pack_slope(pack: QuantTablePack, fn, x: jax.Array, *,
                          extrapolate: bool = False) -> jax.Array:
    """d/dx of the quantized surrogate: (ramp + scale * (c1 - c0)) / delta."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    p, invd, base, segs, scale, zero, ramp = _quant_select(pack, fid, xf)
    i = jnp.clip(jnp.floor((xf - p) * invd), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    codes = pack.codes_for(fid)
    c0 = jnp.take(codes, a, axis=0).astype(jnp.float32)
    c1 = jnp.take(codes, a + 1, axis=0).astype(jnp.float32)
    slope = (ramp + scale * (c1 - c0)) * invd
    if not extrapolate:
        bo = pack.bounds_offset(fid)
        n = pack.n_intervals[fid]
        inside = ((xf >= pack.boundaries[bo]) &
                  (xf < pack.boundaries[bo + n]))
        slope = slope * inside.astype(jnp.float32)
    return slope.astype(dtype)


def make_quant_pack_fn(
    pack: QuantTablePack,
    name: str,
    *,
    use_pallas: bool = True,
    exact_d1=None,
    extrapolate: bool = False,
):
    """Differentiable unary ``f(x)`` served from the quantized pack.

    Mirrors :func:`make_pack_fn`: quantized-table-slope tangent by default,
    ``exact_d1`` for the analytic derivative, ``use_pallas=True`` for the
    fused dequantize-on-read kernel (value + slope in one selector pass on the
    training path).
    """
    fid = pack.fn_id(name)
    if use_pallas:
        from repro.kernels.table_pack_lookup import (
            quant_pack_grad_pallas, quant_pack_lookup_pallas)

        fwd_impl = lambda v: quant_pack_lookup_pallas(
            pack, fid, v, extrapolate=extrapolate)
        fused_grad = lambda v: quant_pack_grad_pallas(
            pack, fid, v, extrapolate=extrapolate)
    else:
        fwd_impl = lambda v: eval_quant_pack_ref(pack, fid, v,
                                                 extrapolate=extrapolate)
        fused_grad = None

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        if exact_d1 is not None:
            y = fwd_impl(x)
            slope = exact_d1(x)
        elif fused_grad is not None:
            y, slope = fused_grad(x)
        else:
            y = fwd_impl(x)
            slope = eval_quant_pack_slope(pack, fid, x, extrapolate=extrapolate)
        return y, slope * dx

    return f


def make_pack_fn(
    pack: TablePack,
    name: str,
    *,
    use_pallas: bool = True,
    exact_d1=None,
    extrapolate: bool = False,
):
    """Differentiable unary ``f(x)`` evaluated through the shared pack.

    Mirrors ``repro.approx.make_table_fn``: table-slope tangent by default
    (what the hardware computes), ``exact_d1`` for the analytic derivative.
    ``use_pallas=True`` routes through the fused pack kernel (one selector pass
    yields value AND slope on the training path).
    """
    fid = pack.fn_id(name)
    if use_pallas:
        from repro.kernels.table_pack_lookup import (
            table_pack_grad_pallas, table_pack_lookup_pallas)
        from repro.parallel.sharding import map_elementwise

        # under a mesh the kernel runs per shard (XLA cannot split it)
        fwd_impl = lambda v: map_elementwise(lambda u: table_pack_lookup_pallas(
            pack, fid, u, extrapolate=extrapolate), v)
        fused_grad = lambda v: map_elementwise(lambda u: table_pack_grad_pallas(
            pack, fid, u, extrapolate=extrapolate), v)
    else:
        fwd_impl = lambda v: eval_pack_ref(pack, fid, v, extrapolate=extrapolate)
        fused_grad = None

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        if exact_d1 is not None:
            y = fwd_impl(x)
            slope = exact_d1(x)
        elif fused_grad is not None:
            y, slope = fused_grad(x)
        else:
            y = fwd_impl(x)
            slope = eval_pack_slope(pack, fid, x, extrapolate=extrapolate)
        return y, slope * dx

    return f


def make_attn_exp_fn(pack: TablePack, *, use_pallas: bool = True):
    """TableFlash exponent: ``exp(z)`` for z <= 0 served from ``exp_neg``.

    The closure flash attention threads as ``exp_fn`` (see
    ``models.attention._flash_inner``).  Both running-softmax arguments are
    non-positive by construction, so the member's [lo, 0] domain covers them
    with an UNDERFLOW-TO-ZERO tail below lo: exp(z) < exp(lo) ~ 1.1e-7 there,
    and returning exactly 0.0 matches f32 ``jnp.exp``'s own underflow for the
    hugely-negative masked-key arguments — masked, empty, and pad slots carry
    weight 0 in both the exact and the table path (a clamp-at-lo tail would
    leak exp(lo) weight per masked slot, dominating E_a at decode's
    ring-buffer occupancy).  The address math still clamps before the
    selector; the zero select is on the raw z.  Fused inside the Pallas
    kernel, explicit on the jnp oracle path — bit-identical under jit.
    Tangent is the table slope, zeroed outside [lo, 0) like every
    non-extrapolating member (the zero tail is constant), so gradients
    through the scan stay finite.  Error contract:
    :mod:`repro.core.attn_error`.
    """
    fid = pack.fn_id("exp_neg")
    lo = float(pack.boundaries[fid, 0])
    if use_pallas:
        from repro.kernels.table_pack_lookup import tableflash_exp_pallas

        fwd_impl = lambda v: tableflash_exp_pallas(pack, v)
    else:
        fwd_impl = lambda v: jnp.where(
            v < lo, 0.0, eval_pack_ref(pack, fid, jnp.maximum(v, lo)))

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        y = fwd_impl(x)
        slope = eval_pack_slope(pack, fid, x)
        return y, slope * dx

    return f


# --------------------------------------------------------------------------------------
# PolyPack — planner-designed degree-d coefficient packs, Horner-evaluated on read.
# --------------------------------------------------------------------------------------


class PolyTablePack(NamedTuple):
    """Device-ready polynomial multi-function pack.

    Member ``fid`` stores ``degree + 1`` coefficient codes per cell in one of
    THREE width-group vectors — ``codes8``/``codes16`` (integer codes) or
    ``codes32`` (the f32 members' raw coefficients, carried through the same
    dequant FMA with ``zero = ramp = 0, scale = 1`` so it is a bit-exact
    identity).  The per-sub-interval dequant params are lane-padded to
    ``max_degree + 1`` lanes for every member: a padded lane dequantizes to
    exactly 0.0 and a leading zero flows through Horner as ``0*t + c = c``,
    so ONE dequant + Horner op sequence serves mixed-degree, mixed-width
    packs (see :class:`repro.core.packing.PolyPackLayout`).
    """

    names: Tuple[str, ...]  # static: member function names (fn_id order)
    n_intervals: Tuple[int, ...]  # static: sub-interval count per member
    degrees: Tuple[int, ...]  # static: interpolation degree per member
    entry_bits: Tuple[int, ...]  # static: 8 | 16 | 32 → which codes vector
    max_degree: int  # static: widest member degree (lane padding target)
    boundaries: jax.Array  # (sum n_f+1,) f32 flat rows
    inv_delta: jax.Array  # (sum n_f,) f32
    base: jax.Array  # (sum n_f,) f32 — GLOBAL index into the width-group codes
    seg_count: jax.Array  # (sum n_f,) f32
    zero: jax.Array  # (sum n_f * (max_degree+1),) f32 lane-padded
    ramp: jax.Array  # (sum n_f * (max_degree+1),) f32 lane-padded
    scale: jax.Array  # (sum n_f * (max_degree+1),) f32 lane-padded
    codes8: jax.Array  # (max(M8,1),) int8
    codes16: jax.Array  # (max(M16,1),) int16
    codes32: jax.Array  # (max(M32,1),) f32 — raw coefficients

    @property
    def n_functions(self) -> int:
        return len(self.names)

    @property
    def max_lanes(self) -> int:
        return self.max_degree + 1

    @property
    def footprint(self) -> int:
        """Stored codes — excludes the 1-entry dummy of an unused width group,
        so it agrees with :class:`PolyPackLayout`'s accounting."""
        m8 = self.codes8.shape[0] if 8 in self.entry_bits else 0
        m16 = self.codes16.shape[0] if 16 in self.entry_bits else 0
        m32 = self.codes32.shape[0] if 32 in self.entry_bits else 0
        return int(m8 + m16 + m32)

    @property
    def footprint_bytes(self) -> int:
        m8 = self.codes8.shape[0] if 8 in self.entry_bits else 0
        m16 = self.codes16.shape[0] if 16 in self.entry_bits else 0
        m32 = self.codes32.shape[0] if 32 in self.entry_bits else 0
        return int(m8 + 2 * m16 + 4 * m32)

    def fn_id(self, name: str) -> int:
        return _member_id(self.names, name)

    def member_id(self, fn) -> int:
        """Name or integer fn_id -> validated index (KeyError otherwise)."""
        return _member_id(self.names, fn)

    def bounds_offset(self, fid: int) -> int:
        return sum(n + 1 for n in self.n_intervals[:fid])

    def lane_offset(self, fid: int) -> int:
        return sum(self.n_intervals[:fid])

    def codes_for(self, fid: int) -> jax.Array:
        bits = self.entry_bits[fid]
        return (self.codes8 if bits == 8
                else self.codes16 if bits == 16 else self.codes32)

    def routing_scalars(self) -> Tuple[np.ndarray, ...]:
        """Prefetched scalar operands for dynamic fn_id dispatch — the quant
        tuple plus the per-member coefficient stride ``degree + 1``:
        ``(n_arr, bounds_offsets, lane_offsets, entry_bits, strides)``."""
        F = self.n_functions
        return (np.asarray(self.n_intervals, dtype=np.int32),
                np.asarray([self.bounds_offset(f) for f in range(F)], np.int32),
                np.asarray([self.lane_offset(f) for f in range(F)], np.int32),
                np.asarray(self.entry_bits, dtype=np.int32),
                np.asarray([d + 1 for d in self.degrees], dtype=np.int32))


def from_poly_layout(layout: PolyPackLayout) -> PolyTablePack:
    if max(len(layout.codes8), len(layout.codes16),
           len(layout.codes32)) >= (1 << 24):
        raise ValueError("pack footprint exceeds f32 exact-integer range")

    def codes_arr(codes: np.ndarray, dtype) -> jax.Array:
        if len(codes) == 0:  # keep a 1-entry dummy so the operand stays valid
            return jnp.zeros((1,), dtype=dtype)
        return jnp.asarray(codes, dtype=dtype)

    f32 = lambda a: jnp.asarray(np.asarray(a, dtype=np.float64),
                                dtype=jnp.float32)
    return PolyTablePack(
        names=layout.names,
        n_intervals=layout.n_intervals,
        degrees=layout.degrees,
        entry_bits=layout.entry_bits,
        max_degree=layout.max_degree,
        boundaries=f32(layout.boundaries),
        inv_delta=f32(layout.inv_delta),
        base=f32(layout.base),
        seg_count=f32(layout.seg_count),
        zero=f32(layout.zero),
        ramp=f32(layout.ramp),
        scale=f32(layout.scale),
        codes8=codes_arr(layout.codes8, jnp.int8),
        codes16=codes_arr(layout.codes16, jnp.int16),
        codes32=codes_arr(layout.codes32, jnp.float32),
    )


def build_poly_pack(
    names: Sequence[str],
    e_a: float,
    *,
    budget_bytes: Optional[int] = None,
    rho: float = 0.9,
    dtype: str = "auto",
    algorithm: str = "hierarchical",
    omega: float = 0.3,
    intervals: Optional[dict] = None,
) -> PolyTablePack:
    """Planner-driven pack: ``repro.core.design.plan`` picks one (degree,
    dtype) candidate per function — cheapest when ``budget_bytes=None``,
    preferred-then-downgraded to fit a byte budget otherwise — and the chosen
    members fuse into one device artifact.  ``dtype`` narrows the planner's
    menu ('auto' keeps f32/int16/int8 all open); ``rho`` splits e_a between
    interpolation and code rounding for the integer candidates."""
    from repro.core import design

    dtypes = design.POLY_DTYPES if dtype == "auto" else (dtype,)
    p = design.plan(list(names), e_a, budget_bytes, dtypes=dtypes,
                    algorithm=algorithm, omega=omega, rho=rho,
                    intervals=intervals)
    return from_poly_layout(poly_pack_layout(list(p.members)))


def _poly_select(pack: PolyTablePack, fid: int, xf: jax.Array):
    """Selector + gathers against member ``fid``'s ragged lane segment; the
    dequant planes come back with a trailing ``max_degree + 1`` lane axis."""
    bo, lo = pack.bounds_offset(fid), pack.lane_offset(fid)
    n = pack.n_intervals[fid]
    lmax = pack.max_lanes
    brow = pack.boundaries[bo : bo + n + 1]
    j = select_interval(brow, n, xf)
    p = jnp.take(brow, j, axis=0)
    invd = jnp.take(pack.inv_delta[lo : lo + n], j, axis=0)
    base = jnp.take(pack.base[lo : lo + n], j, axis=0)
    segs = jnp.take(pack.seg_count[lo : lo + n], j, axis=0)
    lanes = slice(lo * lmax, (lo + n) * lmax)
    zero = jnp.take(pack.zero[lanes].reshape(n, lmax), j, axis=0)
    ramp = jnp.take(pack.ramp[lanes].reshape(n, lmax), j, axis=0)
    scale = jnp.take(pack.scale[lanes].reshape(n, lmax), j, axis=0)
    return p, invd, base, segs, zero, ramp, scale


def _poly_coeffs(pack: PolyTablePack, fid: int, base, i, zero, ramp, scale):
    """Gather + dequantize the cell's ``degree + 1`` monomial coefficients.

    Code of cell ``i``, lane ``l`` lives at ``base + i*(degree+1) + l`` in the
    member's width group; the dequant FMA ``(zero + ramp*i) + scale*q`` is the
    quant-pack sequence per lane (identity for f32 members).
    """
    codes = pack.codes_for(fid)
    stride = float(pack.degrees[fid] + 1)
    cs = []
    for l in range(pack.degrees[fid] + 1):
        a = (base + i * stride + float(l)).astype(jnp.int32)
        q = jnp.take(codes, a, axis=0).astype(jnp.float32)
        cs.append((zero[..., l] + ramp[..., l] * i) + scale[..., l] * q)
    return cs


def poly_horner(cs, t):
    """p(t) with monomial coefficients ``cs[k]`` (constant term first)."""
    y = cs[-1]
    for c in reversed(cs[:-1]):
        y = y * t + c
    return y


def poly_horner_d1(cs, t):
    """p'(t) in the derivative Horner form the kernels mirror."""
    if len(cs) == 1:
        return jnp.zeros_like(t)
    g = cs[-1] * float(len(cs) - 1)
    for k in range(len(cs) - 2, 0, -1):
        g = g * t + cs[k] * float(k)
    return g


def eval_poly_pack_ref(pack: PolyTablePack, fn, x: jax.Array, *,
                       extrapolate: bool = False) -> jax.Array:
    """Pure-jnp dequantize + Horner oracle — bit-identical to the Pallas
    kernel.  ``extrapolate=True`` continues past the cell grid along the
    tangent at the clamped coordinate: ``y = p(tc) + p'(tc) * (t - tc)``."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    p, invd, base, segs, zero, ramp, scale = _poly_select(pack, fid, xf)
    u = (xf - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    cs = _poly_coeffs(pack, fid, base, i, zero, ramp, scale)
    t = u - i
    tc = jnp.clip(t, 0.0, 1.0)
    y = poly_horner(cs, tc)
    if extrapolate:
        y = y + poly_horner_d1(cs, tc) * (t - tc)
    return y.astype(dtype)


def eval_poly_pack_slope(pack: PolyTablePack, fn, x: jax.Array, *,
                         extrapolate: bool = False) -> jax.Array:
    """d/dx of the polynomial surrogate: ``p'(tc) / delta`` (the tangent the
    extrapolating value path continues along), masked outside the domain when
    not extrapolating."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    p, invd, base, segs, zero, ramp, scale = _poly_select(pack, fid, xf)
    u = (xf - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    cs = _poly_coeffs(pack, fid, base, i, zero, ramp, scale)
    tc = jnp.clip(u - i, 0.0, 1.0)
    slope = poly_horner_d1(cs, tc) * invd
    if not extrapolate:
        bo = pack.bounds_offset(fid)
        n = pack.n_intervals[fid]
        inside = ((xf >= pack.boundaries[bo]) &
                  (xf < pack.boundaries[bo + n]))
        slope = slope * inside.astype(jnp.float32)
    return slope.astype(dtype)


def make_poly_pack_fn(
    pack: PolyTablePack,
    name: str,
    *,
    use_pallas: bool = True,
    exact_d1=None,
    extrapolate: bool = False,
):
    """Differentiable unary ``f(x)`` served from the polynomial pack.

    Mirrors :func:`make_quant_pack_fn`: Horner-slope tangent by default,
    ``exact_d1`` for the analytic derivative, ``use_pallas=True`` for the
    fused dequantize + Horner kernel (value + slope in one selector pass on
    the training path).
    """
    fid = pack.fn_id(name)
    if use_pallas:
        from repro.kernels.table_pack_lookup import (
            poly_pack_grad_pallas, poly_pack_lookup_pallas)

        fwd_impl = lambda v: poly_pack_lookup_pallas(
            pack, fid, v, extrapolate=extrapolate)
        fused_grad = lambda v: poly_pack_grad_pallas(
            pack, fid, v, extrapolate=extrapolate)
    else:
        fwd_impl = lambda v: eval_poly_pack_ref(pack, fid, v,
                                                extrapolate=extrapolate)
        fused_grad = None

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        if exact_d1 is not None:
            y = fwd_impl(x)
            slope = exact_d1(x)
        elif fused_grad is not None:
            y, slope = fused_grad(x)
        else:
            y = fwd_impl(x)
            slope = eval_poly_pack_slope(pack, fid, x, extrapolate=extrapolate)
        return y, slope * dx

    return f


# --------------------------------------------------------------------------------------
# ShardedPack — the pack's values vector partitioned over the 'model' mesh axis.
# --------------------------------------------------------------------------------------
#
# The packs above are REPLICATED: every core pins the whole values vector in
# VMEM.  Once the pack outgrows a core's budget, the values are instead
# partitioned at sub-interval granularity (core.packing.shard_pack_layout) and
# each shard answers ONLY the elements whose selected sub-interval it owns:
# every shard runs the full (replicated, small) comparator plane, gathers from
# its LOCAL slice with the rebased base, masks unowned elements to zero, and
# the shard contributions combine by summation — psum over the 'model' axis
# under shard_map, a plain sum over the stacked shard axis off-mesh.  Exactly
# one shard owns any selected sub-interval, so the sum adds one real value and
# S-1 zeros: the result is BIT-IDENTICAL to the replicated pack (x + 0.0 == x
# for every float x), which tests/test_sharded_pack.py asserts per function.


class ShardedTablePack(NamedTuple):
    """Device-ready sharded multi-function pack.

    ``values`` carries one PADDED slice per shard (stacked so the shard axis
    can be laid over the 'model' mesh axis); ``local_base``/``owned`` are the
    per-shard planes (rebased addresses + ownership mask); the selector
    metadata stays replicated.  See :class:`repro.core.packing.ShardedPackLayout`.
    """

    names: Tuple[str, ...]  # static: member function names (fn_id order)
    n_intervals: Tuple[int, ...]  # static: real sub-interval count per member
    n_shards: int  # static: width of the shard (mesh 'model') axis
    boundaries: jax.Array  # (F, n_max+1) f32, right-padded +inf  [replicated]
    inv_delta: jax.Array  # (F, n_max)   f32                      [replicated]
    seg_count: jax.Array  # (F, n_max)   f32                      [replicated]
    local_base: jax.Array  # (S, F, n_max) f32 — SHARD-LOCAL values index
    owned: jax.Array  # (S, F, n_max) f32 — 1.0 where shard s owns (f, j)
    values: jax.Array  # (S, m_max)   f32 — per-shard padded slices

    @property
    def n_functions(self) -> int:
        return len(self.names)

    @property
    def n_max(self) -> int:
        return self.inv_delta.shape[1]

    @property
    def footprint_per_shard(self) -> int:
        """Padded per-shard entry count — the VMEM high-water every core pays."""
        return self.values.shape[1]

    def fn_id(self, name: str) -> int:
        return _member_id(self.names, name)

    def member_id(self, fn) -> int:
        """Name or integer fn_id -> validated index (KeyError otherwise)."""
        return _member_id(self.names, fn)

    def routing_scalars(self) -> Tuple[np.ndarray, ...]:
        """Prefetched scalar operands for dynamic fn_id dispatch (same contract
        as :meth:`TablePack.routing_scalars`)."""
        return (np.asarray(self.n_intervals, dtype=np.int32),)


def from_sharded_layout(slayout: ShardedPackLayout,
                        dtype=jnp.float32) -> ShardedTablePack:
    if slayout.max_shard_entries >= (1 << 24):
        raise ValueError("shard slice exceeds f32 exact-integer range")
    lay = slayout.layout
    S, m_max = slayout.n_shards, slayout.max_shard_entries
    vals = np.zeros((S, m_max), dtype=np.float64)
    for s in range(S):
        sv = slayout.shard_values(s)
        vals[s, : len(sv)] = sv
    lb = np.zeros((S,) + slayout.owner.shape, dtype=np.float64)
    own = np.zeros((S,) + slayout.owner.shape, dtype=np.float64)
    for s in range(S):
        mask = slayout.owner == s
        lb[s][mask] = slayout.local_base[mask]
        own[s][mask] = 1.0
    return ShardedTablePack(
        names=lay.names,
        n_intervals=lay.n_intervals,
        n_shards=S,
        boundaries=jnp.asarray(lay.boundaries, dtype=dtype),
        inv_delta=jnp.asarray(lay.inv_delta, dtype=dtype),
        seg_count=jnp.asarray(lay.seg_count.astype(np.float64), dtype=dtype),
        local_base=jnp.asarray(lb, dtype=dtype),
        owned=jnp.asarray(own, dtype=dtype),
        values=jnp.asarray(vals, dtype=dtype),
    )


def shard_pack(pack_or_specs, n_shards: int) -> ShardedTablePack:
    """Shard already-built TableSpecs (or a PackLayout) into a runtime pack."""
    layout = (pack_or_specs if isinstance(pack_or_specs, PackLayout)
              else pack_layout(list(pack_or_specs)))
    return from_sharded_layout(shard_pack_layout(layout, n_shards))


def build_sharded_pack(
    names: Sequence[str],
    e_a: float,
    n_shards: int,
    *,
    algorithm: str = "hierarchical",
    omega: float = 0.3,
    intervals: Optional[dict] = None,
) -> ShardedTablePack:
    """Design flow for every name, fused into one pack, sharded ``n_shards`` ways."""
    intervals = intervals or {}
    specs = []
    for name in names:
        lo, hi = intervals.get(name, (None, None))
        specs.append(cached_table(name, e_a, lo, hi, algorithm=algorithm,
                                  omega=omega))
    return shard_pack(specs, n_shards)


def shard_contrib_ref(values_s, lbase_row, own_row, brow, invd_row, segs_row,
                      n: int, xf: jax.Array, *, extrapolate: bool,
                      slope: bool = False) -> jax.Array:
    """ONE shard's masked contribution — the sharded-lookup contract.

    Runs the replicated comparator plane, gathers from the LOCAL values slice
    at the rebased address, and zeroes elements whose selected sub-interval
    this shard does not own.  The owner shard executes exactly the replicated
    pack's compare/gather/FMA sequence on the same f32 numbers (the slice
    holds the same entries, only re-addressed), so summing the S contributions
    reproduces ``eval_pack_ref``/``eval_pack_slope`` bit for bit.  Shared by
    the jnp oracle, the shard_map mesh body, and (as the reference for) the
    Pallas shard kernel.
    """
    j = select_interval(brow, n, xf)
    p = jnp.take(brow, j, axis=0)
    invd = jnp.take(invd_row, j, axis=0)
    base = jnp.take(lbase_row, j, axis=0)
    segs = jnp.take(segs_row, j, axis=0)
    own = jnp.take(own_row, j, axis=0)
    u = (xf - p) * invd
    i = jnp.clip(jnp.floor(u), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    # clip: unowned elements may address past the local slice; they are masked
    y0 = jnp.take(values_s, a, axis=0, mode="clip")
    y1 = jnp.take(values_s, a + 1, axis=0, mode="clip")
    if slope:
        out = (y1 - y0) * invd
        if not extrapolate:
            inside = (xf >= brow[0]) & (xf < brow[n])
            out = out * inside.astype(jnp.float32)
    else:
        t = u - i
        if not extrapolate:
            t = jnp.clip(t, 0.0, 1.0)
        out = y0 + t * (y1 - y0)
    return jnp.where(own > 0, out, 0.0)


def _sharded_sum_ref(pack: ShardedTablePack, fid: int, xf: jax.Array,
                     extrapolate: bool, slope: bool) -> jax.Array:
    out = None
    for s in range(pack.n_shards):
        c = shard_contrib_ref(
            pack.values[s], pack.local_base[s, fid], pack.owned[s, fid],
            pack.boundaries[fid], pack.inv_delta[fid], pack.seg_count[fid],
            pack.n_intervals[fid], xf, extrapolate=extrapolate, slope=slope)
        out = c if out is None else out + c
    return out


def eval_sharded_ref(pack: ShardedTablePack, fn, x: jax.Array, *,
                     extrapolate: bool = False) -> jax.Array:
    """Pure-jnp sharded oracle (stacked shard axis, no mesh required) —
    bit-identical to the replicated ``eval_pack_ref``."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    return _sharded_sum_ref(pack, fid, xf, extrapolate, slope=False).astype(dtype)


def eval_sharded_slope(pack: ShardedTablePack, fn, x: jax.Array, *,
                       extrapolate: bool = False) -> jax.Array:
    """d/dx of the sharded surrogate — bit-identical to ``eval_pack_slope``."""
    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    return _sharded_sum_ref(pack, fid, xf, extrapolate, slope=True).astype(dtype)


def _active_pack_mesh(pack: ShardedTablePack):
    """The bound mesh IF its 'model' axis matches the pack's shard count.

    ``use_sharding`` binds the mesh at trace time; when no binding is active
    (or the model axis width differs) the stacked-shard-axis path below is
    used instead — same math, same bits, no distribution.
    """
    from repro.parallel.sharding import current_mesh

    mesh = current_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] == pack.n_shards):
        return mesh
    return None


def eval_sharded_mesh(pack: ShardedTablePack, fn, x: jax.Array, mesh, *,
                      extrapolate: bool = False, use_pallas: bool = False,
                      slope: bool = False) -> jax.Array:
    """Sharded evaluation distributed over ``mesh``'s 'model' axis.

    Each device holds ONE shard's values slice + planes (lay the pack out with
    :func:`repro.parallel.sharding.sharded_pack_pspecs`); the shard_map body
    computes the local masked contribution and a psum over 'model' combines
    them.  psum adds one owner value and S-1 zeros, so the result is
    bit-identical to the replicated pack AND to the off-mesh stacked sum.
    """
    from jax.sharding import PartitionSpec as P

    fid = _resolve(pack, fn)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    n = pack.n_intervals[fid]

    def body(values, lbase, own, xloc):
        if use_pallas:
            from repro.kernels.table_pack_lookup import sharded_shard_contrib_pallas

            c = sharded_shard_contrib_pallas(
                pack.boundaries, pack.inv_delta, pack.seg_count,
                lbase[0], own[0], values[0], xloc,
                fn_id=fid, n_intervals=n, extrapolate=extrapolate, slope=slope)
        else:
            c = shard_contrib_ref(
                values[0], lbase[0, fid], own[0, fid], pack.boundaries[fid],
                pack.inv_delta[fid], pack.seg_count[fid], n, xloc,
                extrapolate=extrapolate, slope=slope)
        return jax.lax.psum(c, "model")

    rep = P(*(None,) * xf.ndim)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("model"), P("model"), P("model"), rep),
        out_specs=rep,
        # pallas_call has no shard_map replication rule; the explicit psum
        # above makes the output replicated regardless
        check_vma=not use_pallas,
    )(pack.values, pack.local_base, pack.owned, xf)
    return out.astype(dtype)


def make_sharded_pack_fn(
    pack: ShardedTablePack,
    name: str,
    *,
    use_pallas: bool = True,
    exact_d1=None,
    extrapolate: bool = False,
):
    """Differentiable unary ``f(x)`` served from the SHARDED pack.

    Mirrors :func:`make_pack_fn`; the forward picks its execution at trace
    time: under an active ``use_sharding`` binding whose 'model' axis is
    ``pack.n_shards`` wide it runs shard_map + psum (each device holds one
    values slice), otherwise it sums the stacked shard contributions on one
    device.  Both are bit-identical to the replicated pack.
    """
    fid = pack.fn_id(name)

    def fwd_impl(v):
        mesh = _active_pack_mesh(pack)
        if mesh is not None:
            return eval_sharded_mesh(pack, fid, v, mesh,
                                     extrapolate=extrapolate,
                                     use_pallas=use_pallas)
        if use_pallas:
            from repro.kernels.table_pack_lookup import sharded_pack_lookup_pallas

            return sharded_pack_lookup_pallas(pack, fid, v,
                                              extrapolate=extrapolate)
        return eval_sharded_ref(pack, fid, v, extrapolate=extrapolate)

    def slope_impl(v):
        mesh = _active_pack_mesh(pack)
        if mesh is not None:
            return eval_sharded_mesh(pack, fid, v, mesh,
                                     extrapolate=extrapolate,
                                     use_pallas=use_pallas, slope=True)
        if use_pallas:
            from repro.kernels.table_pack_lookup import sharded_pack_slope_pallas

            return sharded_pack_slope_pallas(pack, fid, v,
                                             extrapolate=extrapolate)
        return eval_sharded_slope(pack, fid, v, extrapolate=extrapolate)

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        if exact_d1 is not None:
            y = fwd_impl(x)
            slope = exact_d1(x)
        elif use_pallas and _active_pack_mesh(pack) is None:
            # off-mesh training path: fused (value, slope) in one selector pass
            from repro.kernels.table_pack_lookup import sharded_pack_grad_pallas

            y, slope = sharded_pack_grad_pallas(pack, fid, x,
                                                extrapolate=extrapolate)
        else:
            y = fwd_impl(x)
            slope = slope_impl(x)
        return y, slope * dx

    return f


# --------------------------------------------------------------------------------------
# RoutedPack — per-row DYNAMIC fn_id dispatch (one executable, mixed-function batches).
# --------------------------------------------------------------------------------------
#
# The pack kernels above specialize on a static fn_id: a batch mixing functions
# (MoE-style routed activations) needs one compiled executable per member.  The
# routed variant instead takes a per-row ``fn_ids`` vector as a RUNTIME operand
# — ``repro.kernels.routed_pack_lookup`` prefetches it as a scalar operand
# (PrefetchScalarGridSpec) and picks each row's metadata at dispatch time, so
# ONE executable serves every routing.  The oracles here define the contract:
# row i of the output is bit-identical to the static-fn_id dispatch of member
# fn_ids[i] (the where-select literally picks the static per-member values).


def resolve_fn_ids(pack, fn_ids, rows: int) -> jax.Array:
    """Normalize per-row routing ids to a clipped ``(rows,)`` int32 vector.

    Accepts a single name/int (broadcast to every row), a sequence of
    names/ints or a concrete array (each validated against the pack —
    ``KeyError`` on unknowns), or a TRACED int vector (e.g. a router output
    under jit).  Traced ids cannot be validated at trace time; they are
    clamped to the member range, matching the kernels' clamped metadata
    reads.
    """
    if isinstance(fn_ids, (str, int, np.integer)):
        ids = np.full((rows,), pack.member_id(fn_ids), dtype=np.int32)
    elif isinstance(fn_ids, jax.core.Tracer):
        ids = jnp.asarray(fn_ids, dtype=jnp.int32)
    else:  # concrete sequence/array (names or ints): validate every id
        seq = fn_ids if isinstance(fn_ids, (list, tuple)) else np.asarray(fn_ids)
        ids = np.asarray([pack.member_id(f) for f in seq], dtype=np.int32)
    if ids.shape != (rows,):
        raise ValueError(
            f"fn_ids shape {ids.shape} does not match the {rows} leading rows "
            f"of x (one function id per row)")
    return jnp.clip(jnp.asarray(ids, dtype=jnp.int32), 0, pack.n_functions - 1)


def routed_extr_flags(pack, extrapolate) -> np.ndarray:
    """Per-member edge-handling flags as the int32 runtime operand the routed
    kernels gather by fn_id: a single bool applies to every member, a sequence
    gives one flag per member (linear-asymptote members extrapolate, flat ones
    keep the hardware clamp)."""
    if isinstance(extrapolate, (bool, np.bool_, int)):
        flags = (bool(extrapolate),) * pack.n_functions
    else:
        flags = tuple(bool(e) for e in extrapolate)
        if len(flags) != pack.n_functions:
            raise ValueError(
                f"extrapolate needs one flag per member ({pack.n_functions}), "
                f"got {len(flags)}")
    return np.asarray(flags, dtype=np.int32)


def _routed_where(pack, fn_ids, x, member_eval, extrapolate):
    """Row-select over the static per-member evaluations (the routed oracle)."""
    ids = resolve_fn_ids(pack, fn_ids, x.shape[0])
    extr = routed_extr_flags(pack, extrapolate)
    sel = (x.shape[0],) + (1,) * (x.ndim - 1)
    y = None
    for f in range(pack.n_functions):
        yf = member_eval(f, bool(extr[f]))
        y = yf if y is None else jnp.where((ids == f).reshape(sel), yf, y)
    return y


def eval_routed_ref(pack: TablePack, fn_ids, x: jax.Array, *,
                    extrapolate=False) -> jax.Array:
    """Pure-jnp routed oracle: row i of ``x`` through member ``fn_ids[i]`` —
    bit-identical to the corresponding static dispatches."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_pack_ref(pack, f, x, extrapolate=e), extrapolate)


def eval_routed_slope(pack: TablePack, fn_ids, x: jax.Array, *,
                      extrapolate=False) -> jax.Array:
    """d/dx of the routed surrogate (per-row static table slopes)."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_pack_slope(pack, f, x, extrapolate=e), extrapolate)


def eval_routed_quant_ref(pack: QuantTablePack, fn_ids, x: jax.Array, *,
                          extrapolate=False) -> jax.Array:
    """Routed dequantize-on-read oracle over the quantized pack."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_quant_pack_ref(pack, f, x, extrapolate=e), extrapolate)


def eval_routed_quant_slope(pack: QuantTablePack, fn_ids, x: jax.Array, *,
                            extrapolate=False) -> jax.Array:
    """d/dx of the routed quantized surrogate."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_quant_pack_slope(pack, f, x, extrapolate=e),
        extrapolate)


def eval_routed_poly_ref(pack: PolyTablePack, fn_ids, x: jax.Array, *,
                         extrapolate=False) -> jax.Array:
    """Routed dequantize + Horner oracle over the polynomial pack."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_poly_pack_ref(pack, f, x, extrapolate=e), extrapolate)


def eval_routed_poly_slope(pack: PolyTablePack, fn_ids, x: jax.Array, *,
                           extrapolate=False) -> jax.Array:
    """d/dx of the routed polynomial surrogate."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_poly_pack_slope(pack, f, x, extrapolate=e),
        extrapolate)


def eval_routed_sharded_ref(pack: ShardedTablePack, fn_ids, x: jax.Array, *,
                            extrapolate=False) -> jax.Array:
    """Routed oracle over the SHARDED pack: row i through member ``fn_ids[i]``
    with each member's value summed from its shard contributions."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_sharded_ref(pack, f, x, extrapolate=e), extrapolate)


def eval_routed_sharded_slope(pack: ShardedTablePack, fn_ids, x: jax.Array, *,
                              extrapolate=False) -> jax.Array:
    """d/dx of the routed sharded surrogate."""
    return _routed_where(
        pack, fn_ids, x,
        lambda f, e: eval_sharded_slope(pack, f, x, extrapolate=e), extrapolate)


def make_routed_fn(
    pack,
    fn_ids,
    *,
    use_pallas: bool = True,
    extrapolate=False,
):
    """Differentiable per-row routed ``f(x)``: row i of ``x`` (leading axis)
    is served by member ``fn_ids[i]`` of the pack — f32 (:class:`TablePack`)
    or quantized (:class:`QuantTablePack`) — from ONE compiled executable.

    ``fn_ids`` may be names/ints (validated here) or a traced int vector (an
    MoE router output): the ids are a runtime operand of the scalar-prefetch
    kernels, so re-routing never recompiles.  ``extrapolate`` is one flag or a
    per-member sequence (mixed edge semantics in a single call).  The tangent
    is the per-row table slope (what the hardware computes), fused with the
    value pass in the Pallas path.
    """
    quant = isinstance(pack, QuantTablePack)
    poly = isinstance(pack, PolyTablePack)
    sharded = isinstance(pack, ShardedTablePack)
    if use_pallas:
        from repro.kernels.routed_pack_lookup import (
            routed_pack_grad_pallas, routed_pack_lookup_pallas,
            routed_poly_pack_grad_pallas, routed_poly_pack_lookup_pallas,
            routed_quant_pack_grad_pallas, routed_quant_pack_lookup_pallas,
            sharded_routed_pack_grad_pallas, sharded_routed_pack_lookup_pallas)

        if sharded:
            lookup, gradk = (sharded_routed_pack_lookup_pallas,
                             sharded_routed_pack_grad_pallas)
        elif poly:
            lookup, gradk = (routed_poly_pack_lookup_pallas,
                             routed_poly_pack_grad_pallas)
        elif quant:
            lookup, gradk = (routed_quant_pack_lookup_pallas,
                             routed_quant_pack_grad_pallas)
        else:
            lookup, gradk = routed_pack_lookup_pallas, routed_pack_grad_pallas
        fwd_impl = lambda v: lookup(pack, fn_ids, v, extrapolate=extrapolate)
        fused_grad = lambda v: gradk(pack, fn_ids, v, extrapolate=extrapolate)
    else:
        if sharded:
            ref, slope_ref = eval_routed_sharded_ref, eval_routed_sharded_slope
        elif poly:
            ref, slope_ref = eval_routed_poly_ref, eval_routed_poly_slope
        elif quant:
            ref, slope_ref = eval_routed_quant_ref, eval_routed_quant_slope
        else:
            ref, slope_ref = eval_routed_ref, eval_routed_slope
        fwd_impl = lambda v: ref(pack, fn_ids, v, extrapolate=extrapolate)
        fused_grad = None

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        if fused_grad is not None:
            y, slope = fused_grad(x)
        else:
            y = fwd_impl(x)
            slope = slope_ref(pack, fn_ids, x, extrapolate=extrapolate)
        return y, slope * dx

    return f


def make_routed_unary_fn(
    pack,
    name,
    *,
    use_pallas: bool = True,
    exact_d1=None,
    extrapolate: bool = False,
):
    """Shape-agnostic unary ``f(x)`` served through the ROUTED dispatch path
    with uniform fn_ids — what ``ApproxConfig(mode="routed_pack").unary``
    builds.  Unlike :func:`make_pack_fn`, the member identity is a runtime
    operand: every member's unary shares one compiled executable per input
    shape.  The jnp fallback (``use_pallas=False``) evaluates the static
    oracle — bit-identical to the routed kernel by the dispatch contract.
    """
    quant = isinstance(pack, QuantTablePack)
    poly = isinstance(pack, PolyTablePack)
    fid = pack.member_id(name)
    ids = jnp.full((1,), fid, dtype=jnp.int32)
    if use_pallas:
        from repro.kernels.routed_pack_lookup import (
            routed_pack_grad_pallas, routed_pack_lookup_pallas,
            routed_poly_pack_grad_pallas, routed_poly_pack_lookup_pallas,
            routed_quant_pack_grad_pallas, routed_quant_pack_lookup_pallas)

        if poly:
            lookup, gradk = (routed_poly_pack_lookup_pallas,
                             routed_poly_pack_grad_pallas)
        elif quant:
            lookup, gradk = (routed_quant_pack_lookup_pallas,
                             routed_quant_pack_grad_pallas)
        else:
            lookup, gradk = routed_pack_lookup_pallas, routed_pack_grad_pallas
        fwd_impl = lambda v: lookup(
            pack, ids, v.reshape(1, -1), extrapolate=extrapolate
        ).reshape(v.shape)
        fused_grad = lambda v: tuple(
            r.reshape(v.shape) for r in gradk(
                pack, ids, v.reshape(1, -1), extrapolate=extrapolate))
    else:
        if poly:
            ref, slope_ref = eval_poly_pack_ref, eval_poly_pack_slope
        elif quant:
            ref, slope_ref = eval_quant_pack_ref, eval_quant_pack_slope
        else:
            ref, slope_ref = eval_pack_ref, eval_pack_slope
        fwd_impl = lambda v: ref(pack, fid, v, extrapolate=extrapolate)
        fused_grad = None

    @jax.custom_jvp
    def f(x):
        return fwd_impl(x)

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (dx,) = primals, tangents
        if exact_d1 is not None:
            y = fwd_impl(x)
            slope = exact_d1(x)
        elif fused_grad is not None:
            y, slope = fused_grad(x)
        else:
            y = fwd_impl(x)
            slope = slope_ref(pack, fid, x, extrapolate=extrapolate)
        return y, slope * dx

    return f


# --------------------------------------------------------------------------------------
# Telemetry probes (repro.obs device telemetry; see ApproxConfig._maybe_instrument_unary)
# --------------------------------------------------------------------------------------


def member_domain(pack, fn):
    """Member ``fn``'s table domain ``[lo, hi)`` as two f32 device scalars.

    Works across pack families: row-padded boundaries (TablePack /
    ShardedTablePack, ``(F, n_max+1)``) index by ``fid``; flat ragged
    boundaries (QuantTablePack / PolyTablePack) index via the member's static
    ``bounds_offset``.  Inputs outside ``[lo, hi)`` hit the hardware clamp (or
    the linear edge extrapolation for ``_EXTRAPOLATE`` activations) — the
    out-of-domain event the telemetry layer counts.
    """
    fid = _resolve(pack, fn)
    n = pack.n_intervals[fid]
    if pack.boundaries.ndim == 2:
        return pack.boundaries[fid, 0], pack.boundaries[fid, n]
    bo = pack.bounds_offset(fid)
    return pack.boundaries[bo], pack.boundaries[bo + n]


def quant_saturation_counts(pack: QuantTablePack, fn, x: jax.Array):
    """(saturated, total) endpoint-code gathers member ``fn`` performs on ``x``.

    A gathered code at the signed extreme of its width (|c| >= 127 for int8,
    >= 32767 for int16) means the per-sub-interval affine quantizer clipped
    that entry — rounding error there can exceed the planner's budget, so the
    saturation RATE (saturated / total) is the quant health signal the
    telemetry layer reports per function.  Reuses the production selector
    (``_quant_select``), so the counted addresses are exactly the ones the
    dequantize-on-read evaluators gather; each lookup touches the two chord
    endpoints, hence ``total == 2 * x.size``.
    """
    fid = _resolve(pack, fn)
    xf = jnp.asarray(x).astype(jnp.float32)
    p, invd, base, segs, _, _, _ = _quant_select(pack, fid, xf)
    i = jnp.clip(jnp.floor((xf - p) * invd), 0.0, segs - 1.0)
    a = (base + i).astype(jnp.int32)
    codes = pack.codes_for(fid)
    qmax = 127 if pack.entry_bits[fid] == 8 else 32767
    c0 = jnp.abs(jnp.take(codes, a, axis=0).astype(jnp.int32))
    c1 = jnp.abs(jnp.take(codes, a + 1, axis=0).astype(jnp.int32))
    sat = jnp.sum((c0 >= qmax).astype(jnp.int32)) + \
        jnp.sum((c1 >= qmax).astype(jnp.int32))
    return sat, 2 * xf.size
