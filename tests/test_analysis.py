"""PackLint's failure paths: every rule class must FIRE on a seeded violation.

The green direction (the real registry passes) is covered by the
``tools/check_contracts.py`` CI gate and a slow-marked full run here; these
tests prove the rules have *power* — an injected f64 constant, a
``debug_callback`` on the obs-off path, a weak-type cache-key drift, an
inflated pack operand, and a telemetry-on closure each trip their rule.

Also: direct malformed-input unit tests for ``tools/check_trace.py``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, jaxpr_lint as jl
from repro.analysis.report import Finding, Report
from repro.kernels.table_pack_lookup import table_pack_lookup_pallas

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_trace  # noqa: E402

X = np.linspace(-2.0, 2.0, 512).astype(np.float32)


@pytest.fixture(scope="module")
def ctx():
    # two functions keep every pack build small; log brings a foldable
    # member (and its log_core core) into the pack for the folded modes
    return contracts.LintContext(funcs=("tanh", "log"))


# --------------------------------------------------------------------------------------
# Rule 1 — f64 leakage
# --------------------------------------------------------------------------------------

class TestF64Rule:
    def test_seeded_f64_constant_fires(self):
        # under default config jax silently downcasts f64 consts, which is
        # itself a contract violation but an invisible one; x64 mode makes
        # the leak visible to the lint exactly as it would be on a host
        # where the design layer's np.float64 escaped into a closure
        table = np.linspace(0.0, 1.0, 8)  # np.float64, like a design table

        with jax.enable_x64(True):
            traced = jl.trace(lambda v: v + table.sum(), X.astype(np.float64))
            hits = jl.find_wide_dtypes(traced)
        assert hits, "injected f64 constant must be flagged"
        assert any("float64" in h for h in hits)

    def test_seeded_f64_artifact_leaf_fires(self):
        art = {"values": jnp.zeros(4), "raw": np.zeros(4, np.float64)}
        hits = jl.array_leaf_wide_dtypes(art)
        assert len(hits) == 1 and "raw" in hits[0]

    def test_clean_closure_passes(self):
        assert jl.find_wide_dtypes(jl.trace(jnp.tanh, X)) == []


# --------------------------------------------------------------------------------------
# Rule 2 — forbidden primitives / allowlists
# --------------------------------------------------------------------------------------

class TestKernelPrimitivesRule:
    def test_seeded_debug_callback_fires(self):
        def leaky(v):
            jax.debug.callback(lambda a: None, v)
            return jnp.tanh(v)

        cbs = jl.closure_callbacks(jl.trace(leaky, X))
        assert cbs, "debug_callback on the obs-off path must be flagged"
        assert jl.closure_callbacks(jl.trace(jnp.tanh, X)) == []

    def test_callback_forbidden_even_if_allowlisted(self):
        # callbacks are forbidden unconditionally: an allowlist row that
        # names one does not whitelist it
        from collections import Counter
        bad = jl.forbidden_primitives(Counter({"debug_callback": 1}),
                                      allowed=frozenset({"debug_callback"}))
        assert bad == ["debug_callback"]

    def test_unallowlisted_primitive_fires(self, ctx):
        traced = ctx.traced("table_pack", "tanh", "value")
        eqn = jl.pallas_eqns(traced)[0]
        bad = contracts.check_kernel(eqn, allowed=frozenset({"add", "mul"}))
        assert any(b.startswith("unallowlisted:") for b in bad)
        # and against its real allowlist the same kernel is clean
        name = jl.kernel_name(eqn)
        assert contracts.check_kernel(eqn, contracts.KERNEL_ALLOWED[name]) == []

    def test_every_registered_kernel_has_an_allowlist(self, ctx):
        for mode in ("table_pack", "quant_pack", "poly_pack", "routed_pack",
                     "sharded_pack", "folded_pack"):
            for kind in ("value", "grad"):
                for eqn in jl.pallas_eqns(ctx.traced(mode, "tanh", kind)):
                    assert jl.kernel_name(eqn) in contracts.KERNEL_ALLOWED


# --------------------------------------------------------------------------------------
# Rule 3 — recompile hazards
# --------------------------------------------------------------------------------------

class TestRecompileRule:
    def test_seeded_weak_type_drift_fires(self):
        # the same logical call made once with a strongly-typed i32 operand
        # and once with weak python scalars: two jit cache keys == recompile
        strong = jnp.arange(4, dtype=jnp.int32)
        weak = jnp.asarray(2.0) * 1  # weak f32 scalar
        k1 = jl.jit_cache_key((strong, jnp.float32(2.0)))
        k2 = jl.jit_cache_key((strong, weak))
        assert k1 != k2
        assert not jl.keys_stable([k1, k2])
        assert jl.weak_leaves((strong, weak)) != []
        assert jl.weak_leaves((strong, jnp.float32(2.0))) == []

    def test_seeded_dtype_drift_fires(self):
        k1 = jl.jit_cache_key((jnp.arange(4, dtype=jnp.int32),))
        k2 = jl.jit_cache_key((jnp.arange(4, dtype=jnp.int16),))
        assert k1 != k2

    def test_static_kwarg_drift_fires(self):
        a = jnp.zeros(4)
        assert jl.jit_cache_key((a,), static={"grad": False}) != \
            jl.jit_cache_key((a,), static={"grad": True})

    def test_reroute_keys_stable_on_real_entry(self, ctx):
        from repro.kernels.routed_pack_lookup import routed_pack_lookup_pallas

        pack = ctx.pack()
        x2d = ctx.x("tanh").reshape(contracts.ROWS, -1)
        keys, weak = contracts.capture_routed_keys(
            routed_pack_lookup_pallas,
            [(pack, "tanh", x2d), (pack, "log", x2d),
             (pack, ["tanh", "log"] * (contracts.ROWS // 2), x2d)])
        assert len(keys) == 3 and jl.keys_stable(keys)
        assert weak == []

    @pytest.mark.slow
    def test_engine_stationarity(self):
        findings = contracts.engine_stationarity_findings()
        assert findings and all(f.ok for f in findings), \
            [f.detail for f in findings if not f.ok]


# --------------------------------------------------------------------------------------
# Rule 4 — VMEM budgets
# --------------------------------------------------------------------------------------

class TestVmemRule:
    def test_seeded_inflated_pack_fires(self, ctx):
        pack = ctx.pack()
        budget = ctx.layout().vmem().padded_bytes
        fat = pack._replace(values=jnp.concatenate([pack.values] * 4))
        traced = jl.trace(
            lambda v: table_pack_lookup_pallas(fat, "tanh", v), ctx.x("tanh"))
        resident = jl.pack_resident_bytes(jl.pallas_eqns(traced)[0])
        finding = contracts.check_budget(resident, budget, "seeded")
        assert not finding.ok
        assert resident > budget

    def test_real_pack_fits(self, ctx):
        traced = ctx.traced("table_pack", "tanh", "value")
        eqn = jl.pallas_eqns(traced)[0]
        resident = jl.pack_resident_bytes(eqn)
        cost = ctx.layout().vmem()
        # the operands the lowered kernel actually carries are exactly the
        # layout's raw accounting: the table as a values plane of whole
        # 128-lane rows in VMEM, the selector metadata in SMEM
        row = 128 * 4
        assert resident == -(-cost.table_bytes // row) * row
        assert jl.pallas_footprint(eqn)["smem_bytes"] == cost.meta_bytes
        assert contracts.check_budget(resident, cost.padded_bytes, "s").ok

    def test_per_shard_budget(self, ctx):
        traced = ctx.traced("sharded_pack", "tanh", "value")
        eqns = jl.pallas_eqns(traced)
        assert len(eqns) == ctx.n_shards  # one launch per shard
        budget = ctx.slayout().vmem().padded_bytes
        for eqn in eqns:
            assert contracts.check_budget(
                jl.pack_resident_bytes(eqn), budget, "s").ok


# --------------------------------------------------------------------------------------
# Rule 5 — obs-off structural identity
# --------------------------------------------------------------------------------------

class TestObsIdentityRule:
    def test_telemetry_on_closure_differs(self, ctx):
        # the detector must have power: with device_telemetry actually ON the
        # instrumented closure is structurally DIFFERENT from the obs-never
        # closure (that difference is what rule 5 proves absent when off)
        from repro import obs
        from repro.approx import ApproxConfig

        kw = dict(mode="table_pack", e_a=ctx.e_a,
                  pack_functions=ctx.pack_names)
        try:
            obs.disable()
            fp_never = jl.fingerprint(ApproxConfig(**kw).unary("tanh"), X)
            obs.configure(enabled=True, device_telemetry=True)
            fp_on = jl.fingerprint(ApproxConfig(**kw).unary("tanh"), X)
        finally:
            obs.disable()
        assert fp_never != fp_on
        assert "callback" in fp_on and "callback" not in fp_never

    def test_disabled_closure_identical(self, ctx):
        from repro.approx import ApproxConfig

        fp_never, fp_disabled = contracts.obs_identity_fingerprints(
            lambda: ApproxConfig(mode="table_pack", e_a=ctx.e_a,
                                 pack_functions=ctx.pack_names).unary("tanh"),
            X)
        assert fp_never == fp_disabled

    def test_fingerprint_is_deterministic(self, ctx):
        a = jl.fingerprint(ctx.unary_fn("table_pack", "tanh"), X)
        b = jl.fingerprint(ctx.unary_fn("table_pack", "tanh"), X)
        assert a == b


# --------------------------------------------------------------------------------------
# TableFlash enrollment: the attn_exp closure rides rules 2/4/5 whenever the
# lint pack carries exp_neg — with the same seeded-violation power checks
# --------------------------------------------------------------------------------------

class TestTableFlashLint:
    @pytest.fixture(scope="class")
    def actx(self):
        # a pack that actually carries the exp_neg member TableFlash serves
        return contracts.LintContext(funcs=("tanh", "exp_neg"))

    def test_tableflash_kernel_allowlisted(self, actx):
        for kind in ("value", "grad"):
            eqns = jl.pallas_eqns(actx.attn_traced(kind))
            assert eqns, "attn_exp pallas closure must lower a pallas_call"
            for eqn in eqns:
                name = jl.kernel_name(eqn)
                assert name in contracts.KERNEL_ALLOWED
                assert contracts.check_kernel(
                    eqn, contracts.KERNEL_ALLOWED[name]) == []

    def test_seeded_unallowlisted_primitive_fires(self, actx):
        eqn = jl.pallas_eqns(actx.attn_traced("value"))[0]
        bad = contracts.check_kernel(eqn, allowed=frozenset({"add", "mul"}))
        assert any(b.startswith("unallowlisted:") for b in bad)

    def test_vmem_budget_holds_and_seeded_inflation_fires(self, actx):
        from repro.approx import make_attn_exp_fn

        budget = actx.layout().vmem().padded_bytes
        for kind in ("value", "grad"):
            for eqn in jl.pallas_eqns(actx.attn_traced(kind)):
                assert contracts.check_budget(
                    jl.pack_resident_bytes(eqn), budget, "s").ok
        pack = actx.pack()
        fat = pack._replace(values=jnp.concatenate([pack.values] * 4))
        traced = jl.trace(make_attn_exp_fn(fat, use_pallas=True),
                          actx.attn_x())
        resident = jl.pack_resident_bytes(jl.pallas_eqns(traced)[0])
        assert not contracts.check_budget(resident, budget, "seeded").ok

    def test_attn_exp_obs_off_identical(self, actx):
        from repro.approx import ApproxConfig

        fp_never, fp_disabled = contracts.obs_identity_fingerprints(
            lambda: ApproxConfig(mode="table_pack", e_a=actx.e_a,
                                 pack_functions=actx.pack_names,
                                 attn_table=True).attn_exp(), actx.attn_x())
        assert fp_never == fp_disabled

    def test_telemetry_on_attn_exp_differs(self, actx):
        # power check: with device telemetry ON the instrumented attn_exp is
        # structurally different (a callback appears) — the difference rule 5
        # proves absent when telemetry is off
        from repro import obs
        from repro.approx import ApproxConfig

        kw = dict(mode="table_pack", e_a=actx.e_a,
                  pack_functions=actx.pack_names, attn_table=True)
        try:
            obs.disable()
            fp_never = jl.fingerprint(ApproxConfig(**kw).attn_exp(),
                                      actx.attn_x())
            obs.configure(enabled=True, device_telemetry=True)
            fp_on = jl.fingerprint(ApproxConfig(**kw).attn_exp(),
                                   actx.attn_x())
        finally:
            obs.disable()
        assert fp_never != fp_on
        assert "callback" in fp_on and "callback" not in fp_never

    def test_rules_emit_attn_exp_findings(self, actx):
        rep = contracts.run(actx, rules=["kernel_primitives", "vmem_budget"])
        assert rep.ok, rep.summary()
        subjects = {f.subject for f in rep.findings}
        for s in ("closure:attn_exp/value", "closure:attn_exp/grad",
                  "kernel:_tableflash_kernel[attn_exp/value]",
                  "attn_exp/value", "attn_exp/grad"):
            assert s in subjects, s

    def test_no_exp_neg_pack_skips_cleanly(self, ctx):
        # the base fixture's pack has no exp_neg: no attn findings, no error
        rep = contracts.run(ctx, rules=["vmem_budget"])
        assert rep.ok
        assert not any("attn_exp" in f.subject for f in rep.findings)


# --------------------------------------------------------------------------------------
# The registry end-to-end (subsampled fast; the CLI gates the full matrix)
# --------------------------------------------------------------------------------------

class TestRegistry:
    def test_report_shape(self):
        rep = Report(findings=[Finding("r", "s", True),
                               Finding("r", "t", False, "boom")])
        assert not rep.ok and len(rep.failures()) == 1
        doc = rep.to_dict()
        assert doc["schema"] == "packlint-report-v1"
        assert doc["rules"]["r"]["checked"] == 2
        assert "boom" in rep.summary()

    def test_all_five_rules_registered(self):
        assert set(contracts.RULES) == {
            "f64_leak", "kernel_primitives", "recompile_hazard",
            "vmem_budget", "obs_off_identity"}

    def test_fast_rules_green(self, ctx):
        rep = contracts.run(ctx, rules=["f64_leak", "kernel_primitives",
                                        "vmem_budget"])
        assert rep.ok, rep.summary()
        # auto-enrollment: every registered mode was checked
        subjects = {f.subject for f in rep.findings}
        for mode in contracts.ALL_MODES:
            assert any(s.startswith(f"{mode}/") for s in subjects), mode

    @pytest.mark.slow
    def test_full_registry_green(self, ctx):
        rep = contracts.run(ctx)
        assert rep.ok, rep.summary()


# --------------------------------------------------------------------------------------
# tools/check_trace.py malformed-input handling
# --------------------------------------------------------------------------------------

def _ev(**kw):
    base = {"name": "t", "ph": "i", "pid": 1, "tid": 1, "ts": 1.0}
    base.update(kw)
    return base


class TestCheckTrace:
    def test_clean_trace(self):
        doc = {"traceEvents": [_ev(ph="B"), _ev(ph="E", ts=2.0)]}
        assert check_trace.validate_trace(doc) == []

    def test_top_level_garbage(self):
        assert check_trace.validate_trace(42) == [
            "top level is neither an object nor an array"]
        assert check_trace.validate_trace({"foo": []}) == [
            "top level has no traceEvents array"]
        assert check_trace.validate_trace([]) == ["traceEvents is empty"]

    def test_non_dict_event(self):
        errs = check_trace.validate_trace(["nope"])
        assert any("not an object" in e for e in errs)

    def test_missing_name_and_unknown_phase(self):
        errs = check_trace.validate_trace([_ev(name=""), _ev(ph="Q")])
        assert any("missing name" in e for e in errs)
        assert any("unknown phase 'Q'" in e for e in errs)

    def test_missing_pid_tid_and_ts(self):
        ev = {"name": "t", "ph": "i"}
        errs = check_trace.validate_trace([ev])
        assert sum("missing numeric" in e for e in errs) == 3  # pid, tid, ts

    def test_metadata_exempt_from_ts(self):
        ev = {"name": "process_name", "ph": "M", "pid": 1, "tid": 1}
        assert check_trace.validate_trace([ev]) == []

    def test_backwards_ts(self):
        errs = check_trace.validate_trace([_ev(ts=5.0), _ev(ts=1.0)])
        assert any("ts went backwards" in e for e in errs)

    def test_unbalanced_and_crossed_spans(self):
        errs = check_trace.validate_trace([_ev(ph="E")])
        assert any("E without matching B" in e for e in errs)
        errs = check_trace.validate_trace(
            [_ev(ph="B", name="a"), _ev(ph="B", name="b", ts=2.0),
             _ev(ph="E", name="a", ts=3.0)])
        assert any("not nested" in e for e in errs)
        assert any("never ended" in e for e in errs)

    def test_span_ends_before_it_begins(self):
        # E's ts is checked against the B it closes on the same track; a
        # second track resets monotonicity so only the span check fires
        errs = check_trace.validate_trace(
            [_ev(ph="B", ts=5.0), _ev(ph="E", ts=1.0)])
        assert any("backwards" in e for e in errs)

    def test_x_and_c_payloads(self):
        errs = check_trace.validate_trace([_ev(ph="X", dur=-1)])
        assert any("non-negative dur" in e for e in errs)
        errs = check_trace.validate_trace([_ev(ph="C", args={"q": "high"})])
        assert any("dict of numeric series" in e for e in errs)
        assert check_trace.validate_trace(
            [_ev(ph="X", dur=2), _ev(ph="C", args={"q": 1})]) == []

    def test_main_usage_exit(self):
        with pytest.raises(SystemExit) as ei:
            check_trace.main([])
        assert ei.value.code == 2

    def test_main_failing_file(self, tmp_path):
        p = tmp_path / "t.json"
        p.write_text('{"traceEvents": [{"ph": "Q"}]}')
        with pytest.raises(SystemExit) as ei:
            check_trace.main([str(p)])
        assert ei.value.code == 1
