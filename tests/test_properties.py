"""Hypothesis property tests for the system's invariants.

Invariants from the paper:
  1. Error bound: for any (function, interval, Ea, algorithm, omega), the generated
     table never exceeds Ea anywhere in the interval (Eq. 10 guarantee).
  2. Footprint dominance: any accepted split has footprint <= the Reference footprint
     (splits are only accepted when they reduce).
  3. Partition validity: sorted, spans exactly [lo, hi), no empty sub-intervals.
  4. Monotone Ea: halving Ea never shrinks the Reference footprint.
  5. Fixed-point quantization is idempotent and bounded by half-ULP in range.
  6. QuantPack entry codes: chord-residual affine quantization round-trips
     within the rounding share of the budget, refinement never breaks the
     partition or the stored piecewise-linear function, and the end-to-end
     |f - dequantized table| stays <= Ea for any (function, Ea, rho, width).
  7. Routed dispatch: for ARBITRARY per-row fn_ids assignments, the routed
     kernels/oracles are bit-identical to the corresponding static-fn_id
     dispatches, for both the f32 and the quantized pack.

Profiles: the default ``ci`` profile keeps the unannotated (routing) tests
cheap; ``HYPOTHESIS_PROFILE=nightly`` (the scheduled CI job) runs them with
more examples.  Tests with explicit ``max_examples`` are unaffected.
"""

import math
import os

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

settings.register_profile("ci", max_examples=12, deadline=None)
settings.register_profile("nightly", max_examples=75, deadline=None)
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))

from repro.core import (
    FixedPointFormat,
    build_table,
    chord_residual_ranges,
    get_function,
    quantize_spec,
    refine_for_quantization,
    reference_spacing,
    split,
)
from repro.core.quantize import quant_rounding_limit

FUNCS = ["log", "exp", "tanh", "sigmoid", "gauss", "gelu", "silu", "softplus"]
ALGS = ["reference", "binary", "hierarchical", "sequential"]


def subinterval(name, frac_lo, frac_len):
    """Map two unit floats to a non-degenerate sub-interval of the registry default."""
    lo0, hi0 = get_function(name).interval
    span = hi0 - lo0
    lo = lo0 + frac_lo * span * 0.8
    length = max(span * 0.05, frac_len * (hi0 - lo) * 0.95)
    hi = min(hi0, lo + length)
    if hi - lo < span * 0.02:
        hi = min(hi0, lo + span * 0.02)
    return float(lo), float(hi)


@settings(max_examples=40, deadline=None)
@given(
    name=st.sampled_from(FUNCS),
    alg=st.sampled_from(ALGS),
    frac_lo=st.floats(0.0, 1.0),
    frac_len=st.floats(0.1, 1.0),
    ea_exp=st.floats(-6.0, -2.0),
    omega=st.floats(0.05, 0.9),
)
def test_error_bound_invariant(name, alg, frac_lo, frac_len, ea_exp, omega):
    lo, hi = subinterval(name, frac_lo, frac_len)
    ea = 10.0 ** ea_exp
    ts = build_table(name, ea, lo, hi, algorithm=alg, omega=omega)
    err = ts.max_error_on_grid(n=20_001)
    assert err <= ea * (1 + 1e-6), (name, alg, lo, hi, ea, err)


@settings(max_examples=30, deadline=None)
@given(
    name=st.sampled_from(FUNCS),
    alg=st.sampled_from(["binary", "hierarchical", "sequential"]),
    frac_lo=st.floats(0.0, 1.0),
    frac_len=st.floats(0.1, 1.0),
    ea_exp=st.floats(-6.0, -2.0),
    omega=st.floats(0.05, 0.9),
)
def test_split_never_worse_than_reference(name, alg, frac_lo, frac_len, ea_exp, omega):
    lo, hi = subinterval(name, frac_lo, frac_len)
    ea = 10.0 ** ea_exp
    fn = get_function(name)
    ref = reference_spacing(fn, ea, lo, hi)
    sr = split(alg, name, ea, lo, hi, omega)
    # Eq.13 double-counts shared boundary entries; a 1-interval split == reference.
    # Any accepted split strictly reduced, so footprint <= reference always.
    assert sr.footprint <= ref.footprint + 1, (sr.footprint, ref.footprint)
    # partition validity
    p = sr.partition
    assert p[0] == pytest.approx(lo) and p[-1] == pytest.approx(hi)
    assert np.all(np.diff(p) > 0)


@settings(max_examples=30, deadline=None)
@given(
    name=st.sampled_from(FUNCS),
    frac_lo=st.floats(0.0, 1.0),
    frac_len=st.floats(0.1, 1.0),
    ea_exp=st.floats(-5.0, -2.0),
)
def test_footprint_monotone_in_ea(name, frac_lo, frac_len, ea_exp):
    lo, hi = subinterval(name, frac_lo, frac_len)
    ea = 10.0 ** ea_exp
    fn = get_function(name)
    big = reference_spacing(fn, ea, lo, hi).footprint
    small = reference_spacing(fn, ea / 2.0, lo, hi).footprint
    assert small >= big


@settings(max_examples=50, deadline=None)
@given(
    signed=st.integers(0, 1),
    width=st.integers(4, 32),
    frac=st.integers(0, 30),
    data=st.lists(st.floats(-100, 100, allow_nan=False), min_size=1, max_size=16),
)
def test_fixed_point_idempotent_and_bounded(signed, width, frac, data):
    frac = min(frac, width - signed)
    fmt = FixedPointFormat(signed, width, frac)
    x = np.asarray(data)
    q = fmt.quantize(x)
    np.testing.assert_array_equal(fmt.quantize(q), q)
    in_range = (x >= fmt.min_value) & (x <= fmt.max_value)
    if in_range.any():
        err = np.abs(q[in_range] - x[in_range])
        assert np.max(err) <= fmt.quantization_error_bound() * (1 + 1e-9)


@settings(max_examples=15, deadline=None)
@given(
    name=st.sampled_from(["tanh", "gelu", "log", "sigmoid"]),
    ea_exp=st.floats(-5.0, -2.5),
    rho=st.floats(0.5, 0.95),
    bits=st.sampled_from([8, 16]),
)
def test_quant_round_trip_within_rounding_budget(name, ea_exp, rho, bits):
    """Affine chord-residual codes reconstruct every stored entry within the
    rounding share (1 - rho) * Ea of the budget, at either storage width."""
    ea = 10.0 ** ea_exp
    tol = (1.0 - rho) * ea
    ts = build_table(name, rho * ea)
    refined = refine_for_quantization(ts, quant_rounding_limit(tol, bits))
    assert chord_residual_ranges(refined).max(initial=0.0) <= \
        quant_rounding_limit(tol, bits) * (1 + 1e-12)
    m = quantize_spec(refined, tol, bits, rho=rho, e_a=ea)
    # round trip: dequantized entries vs the f64 table values
    err = np.max(np.abs(m.dequantize() - refined.values))
    assert err <= tol * (1 + 1e-9), (name, ea, rho, bits, err)
    # codes fit the signed storage width
    assert m.codes.min() >= -(2 ** (bits - 1))
    assert m.codes.max() <= 2 ** (bits - 1) - 1
    # refinement kept a valid partition over the same interval
    p = m.spec.boundaries
    assert p[0] == ts.boundaries[0] and p[-1] == ts.boundaries[-1]
    assert np.all(np.diff(p) > 0)


@settings(max_examples=10, deadline=None)
@given(
    name=st.sampled_from(["tanh", "gelu", "log", "sigmoid"]),
    ea_exp=st.floats(-5.0, -2.5),
    rho=st.floats(0.5, 0.95),
    bits=st.sampled_from([8, 16]),
)
def test_quant_end_to_end_error_bound(name, ea_exp, rho, bits):
    """Eq. 10 (at rho*Ea) + rounding <= (1-rho)*Ea compose: the dequantized
    table never exceeds the full budget Ea anywhere in the interval."""
    ea = 10.0 ** ea_exp
    tol = (1.0 - rho) * ea
    ts = build_table(name, rho * ea)
    refined = refine_for_quantization(ts, quant_rounding_limit(tol, bits))
    m = quantize_spec(refined, tol, bits, rho=rho, e_a=ea)
    assert m.max_error_on_grid(n=20_001) <= ea * (1 + 1e-6)


# ------------------------------------------------------------------------------
# 7. Routed dispatch == static dispatch, bitwise, for arbitrary routings.
# ------------------------------------------------------------------------------

ROUTED_FUNCS = ("gelu", "tanh", "log", "sigmoid")
ROUTED_EA = 1e-3  # loose budget: tiny tables, fast pack builds
_ROUTED_PACKS = {}


def _routed_pack(kind):
    if kind not in _ROUTED_PACKS:
        import jax.numpy as jnp  # noqa: F401  (jax import deferred to first use)
        from repro.approx import from_quant_layout, pack_specs
        from repro.core import cached_table, plan_quant_member, quant_pack_layout

        if kind == "f32":
            _ROUTED_PACKS[kind] = pack_specs(
                [cached_table(n, ROUTED_EA) for n in ROUTED_FUNCS])
        else:
            _ROUTED_PACKS[kind] = from_quant_layout(quant_pack_layout(
                [plan_quant_member(n, ROUTED_EA) for n in ROUTED_FUNCS]))
    return _ROUTED_PACKS[kind]


def _routed_case_check(kind, ids, seed, extr):
    import jax
    import jax.numpy as jnp
    from repro.approx.table_pack import eval_routed_quant_ref, eval_routed_ref
    from repro.kernels.routed_pack_lookup import (
        routed_pack_lookup_pallas, routed_quant_pack_lookup_pallas)
    from repro.kernels.table_pack_lookup import (
        quant_pack_lookup_pallas, table_pack_lookup_pallas)

    pack = _routed_pack(kind)
    routed = routed_pack_lookup_pallas if kind == "f32" else \
        routed_quant_pack_lookup_pallas
    static = table_pack_lookup_pallas if kind == "f32" else \
        quant_pack_lookup_pallas
    oracle = eval_routed_ref if kind == "f32" else eval_routed_quant_ref

    x = jnp.asarray(np.random.default_rng(seed)
                    .normal(0, 6, (len(ids), 96)).astype(np.float32))
    got = np.asarray(routed(pack, ids, x, extrapolate=extr))
    # bit-identical to the per-row STATIC dispatches...
    for r, fid in enumerate(ids):
        want = np.asarray(static(pack, fid, x[r], extrapolate=extr))
        np.testing.assert_array_equal(got[r], want, err_msg=f"row {r} fid {fid}")
    # ...and to the jnp where-select oracle, under jit
    ref = np.asarray(jax.jit(
        lambda v: oracle(pack, ids, v, extrapolate=extr))(x))
    np.testing.assert_array_equal(got, ref)


@settings(deadline=None)  # examples count comes from the ci/nightly profile
@given(
    ids=st.lists(st.integers(0, len(ROUTED_FUNCS) - 1), min_size=1, max_size=5),
    seed=st.integers(0, 2**31 - 1),
    extr=st.booleans(),
)
def test_routed_f32_bit_identical_to_static(ids, seed, extr):
    """Invariant 7, f32 pack: any routing == the static dispatches, bitwise."""
    _routed_case_check("f32", ids, seed, extr)


@settings(deadline=None)
@given(
    ids=st.lists(st.integers(0, len(ROUTED_FUNCS) - 1), min_size=1, max_size=5),
    seed=st.integers(0, 2**31 - 1),
    extr=st.booleans(),
)
def test_routed_quant_bit_identical_to_static(ids, seed, extr):
    """Invariant 7, quantized pack (dequantize-on-read + width groups)."""
    _routed_case_check("quant", ids, seed, extr)


@settings(max_examples=30, deadline=None)
@given(
    name=st.sampled_from(FUNCS),
    ea_exp=st.floats(-6.0, -2.0),
    n_cuts=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_any_partition_respects_bound(name, ea_exp, n_cuts, seed):
    """Eq. 11 per sub-interval => bound holds for ARBITRARY partitions, not just
    the three algorithms' outputs (the paper's guarantee is partition-independent)."""
    from repro.core.splitting import _finalize
    from repro.core.spacing import SecondDerivMax

    fn = get_function(name)
    lo, hi = fn.interval
    ea = 10.0 ** ea_exp
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.uniform(lo, hi, size=n_cuts))
    cuts = cuts[(cuts > lo + 1e-6) & (cuts < hi - 1e-6)]
    oracle = SecondDerivMax(fn, lo, hi)
    sr = _finalize(fn, oracle, [lo, *cuts.tolist(), hi], ea, 0.3, "manual")
    ts = build_table(name, ea, lo, hi, algorithm="manual", split_result=sr)
    assert ts.max_error_on_grid(n=20_001) <= ea * (1 + 1e-6)


# ------------------------------------------------------------------------------------
# Invariant 8 (RangeFold): reduction identities through the table path
# ------------------------------------------------------------------------------------
#
# The folded modes promise the MATHEMATICAL identities of the served functions,
# not just pointwise Ea: periodicity for trig, the exp(x)*exp(-x)=1 group law,
# and — the strongest one — bit-exact agreement with the unfolded core lookup
# whenever the argument already lies in the canonical interval (the fold is an
# identity there: k=0, r=x, and the reconstruction multiplies by 2^0 / selects
# quadrant 0).  Subnormals are excluded from the bit-parity properties: XLA
# flushes f32 subnormal ARITHMETIC (DAZ), so the fold's identity guarantee
# starts at the normal range.

_EA_FOLD = 1e-4


def _fold_cfg():
    from repro.approx import ApproxConfig

    return ApproxConfig(mode="folded_pack_ref", e_a=_EA_FOLD)


def _eval_folded(name, xs):
    import jax.numpy as jnp

    from repro.approx.range_fold import eval_folded_ref

    x = np.asarray(xs, np.float32).reshape(1, -1)
    return np.asarray(eval_folded_ref(_fold_cfg().pack(), name, jnp.asarray(x)))[0]


@settings(deadline=None)
@given(x=st.floats(-8.0, 8.0, allow_subnormal=False, width=32))
def test_sin_periodicity_through_table(x):
    """sin(x + 2pi) == sin(x) through the folded table path, within the Ea
    contract on both evaluations plus the f32 rounding of x + 2pi."""
    x2 = np.float32(np.float64(x) + 2.0 * math.pi)
    a, b = _eval_folded("sin", [x, x, x, x]), _eval_folded("sin", [x2] * 4)
    assert abs(float(a[0]) - float(b[0])) <= 2 * (_EA_FOLD * 1.02) + 1e-5


@settings(deadline=None)
@given(x=st.floats(-30.0, 30.0, allow_subnormal=False, width=32))
def test_exp_group_law_through_table(x):
    """exp(x) * exp(-x) == 1 through the folded table: each factor is within
    the RELATIVE contract, so the product is within ~2x of it."""
    e_pos, e_neg = _eval_folded("exp", [x] * 4), _eval_folded("exp", [-x] * 4)
    assert abs(float(e_pos[0]) * float(e_neg[0]) - 1.0) <= 5e-4


@settings(deadline=None)
@given(x=st.floats(float(np.float32(-0.78)), float(np.float32(0.78)),
                 allow_subnormal=False, width=32))
def test_folded_trig_bit_parity_on_core(x):
    """|x| < pi/4: folded sin/cos == the raw core member lookup, BITWISE
    (the fold is an identity and the reconstruction is transparent)."""
    import jax.numpy as jnp

    from repro.approx.range_fold import eval_folded_ref
    from repro.approx.table_pack import eval_pack_ref

    pack = _fold_cfg().pack()
    v = jnp.asarray(np.full((1, 4), x, np.float32))
    for name, core in (("sin", "sin_core"), ("cos", "cos_core")):
        folded = np.asarray(eval_folded_ref(pack, name, v))
        raw = np.asarray(eval_pack_ref(pack, core, v))
        np.testing.assert_array_equal(folded, raw, err_msg=name)


@settings(deadline=None)
@given(x=st.floats(float(np.float32(-0.34)), float(np.float32(0.34)),
                 allow_subnormal=False, width=32))
def test_folded_exp_bit_parity_on_core(x):
    """|x| < ln2/2: folded exp == the raw exp_core lookup bitwise (k = 0)."""
    import jax.numpy as jnp

    from repro.approx.range_fold import eval_folded_ref
    from repro.approx.table_pack import eval_pack_ref

    pack = _fold_cfg().pack()
    v = jnp.asarray(np.full((1, 4), x, np.float32))
    folded = np.asarray(eval_folded_ref(pack, "exp", v))
    raw = np.asarray(eval_pack_ref(pack, "exp_core", v))
    np.testing.assert_array_equal(folded, raw)


# --------------------------------------------------------------------------------------
# Invariant 9 (TableFlash): flash attention is invariant to the kv-chunk split
# --------------------------------------------------------------------------------------


@settings(deadline=None)  # examples count comes from the ci/nightly profile
@given(
    t=st.integers(4, 12),
    sq=st.integers(1, 4),
    window=st.sampled_from([0, 3, 6]),
    causal=st.booleans(),
    clocks=st.booleans(),
    seed=st.integers(0, 2**16),
)
def test_flash_chunk_invariance(t, sq, window, causal, clocks, seed):
    """``_flash_inner``'s running softmax is a reduction: the kv_chunk split
    must not change the output.  Exact path: invariant to f32 accumulation
    order (tight allclose).  Table path: each chunking carries its own
    provable bound vs exact flash, so two chunkings differ by at most the sum
    of their bounds.  Geometry (window masking, per-slot (B, Sq) clocks with
    genuine empty slots) is drawn by hypothesis.  Rows with NO valid key are
    excluded, per the attn_error contract: the running max never leaves the
    finite NEG_INF floor there, so every slot contributes exp(0) = 1 and the
    renormalized garbage depends on the pad count (callers mask such rows)."""
    import jax.numpy as jnp

    from repro.approx import ApproxConfig
    from repro.core.attn_error import flash_abs_bound
    from repro.models.attention import flash_attention

    rng = np.random.default_rng(seed)
    B, G, QG, D = 2, 2, 2, 4
    q = jnp.asarray(rng.normal(0, 1, (B, sq, G, QG, D)), np.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, t, G, D)), np.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, t, G, D)), np.float32)
    if clocks:
        offs = rng.integers(0, 3, B)
        q_pos = jnp.asarray(np.stack([t - sq + o + np.arange(sq)
                                      for o in offs]).astype(np.int32))
        kp = np.tile(np.arange(t, dtype=np.int32), (B, 1))
        kp[:, -1] = -1  # one genuine empty cache slot per row
        k_pos = jnp.asarray(kp)
    else:
        q_pos = jnp.arange(t - sq, t, dtype=jnp.int32)
        k_pos = jnp.arange(t, dtype=jnp.int32)

    # rows with no valid key are outside the contract — mask them out
    qp2 = np.atleast_2d(np.asarray(q_pos))          # (1|B, Sq)
    kp2 = np.atleast_2d(np.asarray(k_pos))          # (1|B, T)
    ok = kp2[:, None, :] >= 0
    if causal:
        ok = ok & (kp2[:, None, :] <= qp2[:, :, None])
    if window > 0:
        ok = ok & (kp2[:, None, :] > qp2[:, :, None] - window)
    row_ok = np.broadcast_to(ok.any(-1), (B, sq))[:, :, None, None, None]

    table_fn = ApproxConfig(mode="table_pack_ref", e_a=1e-4, omega=0.2,
                            attn_table=True).attn_exp()
    ea_eff = 1e-4 * 1.02 + 1e-5
    v_max = float(jnp.max(jnp.abs(v)))
    for exp_fn, label in ((None, "exact"), (table_fn, "table")):
        outs = {}
        for c in (1, 3, 8, t):
            outs[c] = np.asarray(flash_attention(
                q, k, v, q_pos, k_pos, causal=causal, window=window,
                kv_chunk=c, exp_fn=exp_fn)) * row_ok
        for c in (1, 3, 8):
            if exp_fn is None:
                tol = 1e-5  # f32 accumulation-order slop only
            else:
                tol = (flash_abs_bound(ea_eff, t, c, v_max)
                       + flash_abs_bound(ea_eff, t, t, v_max) + 1e-5)
            np.testing.assert_allclose(
                outs[c], outs[t], atol=tol, rtol=0,
                err_msg=f"{label} kv_chunk={c} vs {t}")
