"""PackLint — the repo's standing contracts, checked structurally on traces.

Five rule classes, each registered in :data:`RULES` and auto-enrolled over
the live mode registry (``repro.approx.TABLE_MODES`` plus ``"exact"``) the
same way a new mode joins the conformance matrix — a mode that ships without
being lintable here fails the ``kernel_primitives`` rule's
"unregistered kernel" clause rather than silently skipping:

1. ``f64_leak``        — no design-layer float64/complex128 may appear in any
                         runtime closure's jaxpr or any pack artifact leaf.
2. ``kernel_primitives`` — every Pallas kernel body stays inside its frozen
                         per-entry primitive allowlist: no host callbacks, no
                         infeed/outfeed, no dynamic-shape avals; runtime
                         closures built with observability off contain no
                         callback primitive anywhere.
3. ``recompile_hazard`` — the jit cache key of the routed kernels is invariant
                         across reroutes (captured via a trace-only spy on the
                         real jitted entry), and ContinuousEngine serves a
                         queue from exactly two executables whose signatures
                         are stationary (tick outputs re-feed as inputs with
                         identical avals; no weak types anywhere).
4. ``vmem_budget``     — the VMEM-resident pack operands recovered from each
                         lowered ``pallas_call`` (pinned VMEM planes; SMEM
                         metadata and prefetch rows live in scalar memory)
                         fit the planner's own budget:
                         ``PackLayout/QuantPackLayout.vmem()``,
                         ``PackPlan.vmem()`` (+ the documented device
                         lane-padding allowance), and the per-shard
                         ``ShardedPackLayout.vmem()``.
5. ``obs_off_identity`` — for every mode, the closure built with
                         observability enabled-but-telemetry-off is
                         structurally identical (printed jaxpr equality) to
                         the closure built with observability never imported
                         into the picture at all.

The TableFlash closure (``ApproxConfig.attn_exp`` — the fused exp_neg lookup
flash attention calls from its running-softmax step, docs/table_flash.md) is
enrolled in rules 2, 4, and 5 alongside the mode matrix whenever the lint
pack carries an ``exp_neg`` member.

Everything is derived from ``jax.make_jaxpr`` / ``jax.eval_shape`` traces —
no kernel is ever executed; the numerical side of these contracts lives in
``tests/test_conformance.py``.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import jaxpr_lint as jl
from repro.analysis.report import Finding, Report
from repro.approx import (
    FOLDABLE,
    FOLDED_MODES,
    TABLE_MODES,
    ApproxConfig,
    build_poly_pack,
    eval_folded_ref,
    eval_folded_routed,
    eval_pack_ref,
    eval_poly_pack_ref,
    eval_quant_pack_ref,
    eval_routed_poly_ref,
    eval_routed_quant_ref,
    eval_routed_ref,
    eval_sharded_ref,
    eval_table_ref,
    folded_lookup,
    from_quant_layout,
    from_spec,
    get_exact,
    make_attn_exp_fn,
    make_folded_fn,
    make_folded_routed_unary_fn,
    make_pack_fn,
    make_poly_pack_fn,
    make_quant_pack_fn,
    make_routed_unary_fn,
    make_sharded_pack_fn,
    make_table_fn,
    pack_specs,
    shard_pack,
)
from repro.core import (
    cached_table,
    design,
    function_names,
    get_function,
    pack_layout,
    plan_quant_member,
    quant_pack_layout,
)
from repro.core.packing import shard_pack_layout
from repro.kernels.table_lookup import table_lookup_pallas
from repro.kernels.table_pack_lookup import (
    poly_pack_lookup_pallas,
    quant_pack_lookup_pallas,
    sharded_pack_lookup_pallas,
    table_pack_lookup_pallas,
)
from repro.kernels.routed_pack_lookup import (
    routed_pack_grad_pallas,
    routed_pack_lookup_pallas,
    routed_poly_pack_grad_pallas,
    routed_poly_pack_lookup_pallas,
    routed_quant_pack_grad_pallas,
    routed_quant_pack_lookup_pallas,
)

EA = 1e-4
ROWS = 16  # routed modes reshape the grid into (ROWS, -1) rows
N_GRID = 2048
N_SHARDS = 2
# the fast-tier subsample (mirrors tests/test_conformance.FAST_FUNCS)
FAST_FUNCS = ("gelu", "tanh", "log")
ALL_MODES = tuple(TABLE_MODES) + ("exact",)


# --------------------------------------------------------------------------------------
# Kernel-entry allowlists (rule 2) — keyed by the pallas kernel body's
# registered name (the kernel function's __name__ in kernels/*.py).  A kernel
# that is not listed here FAILS the lint: enrolling a new kernel means adding
# its row, which is the moment to review what it is allowed to do on-device.
# --------------------------------------------------------------------------------------

# Frozen from the lowered kernel bodies at enrollment time (comparator-plane
# select + gather/FMA arithmetic; ``jit`` covers jnp.clip/take sub-calls;
# ``get``/``swap`` are the pallas ref reads/writes; ``select_n`` is the
# shared selector's running compare-and-select).
_BASE = frozenset({
    "add", "broadcast_in_dim", "convert_element_type", "floor", "gather",
    "ge", "get", "jit", "max", "min", "mul", "reduce_sum", "select_n",
    "slice", "sub", "swap",
})
# table_pack's values-plane read: row/lane split of the address, per-row
# lane gather (take_along_axis), row select and the lane-chunk concatenation
_PLANE = frozenset({"and", "concatenate", "eq", "reshape",
                    "shift_right_arithmetic"})
# grad kernels add the in-domain mask (d/dx of the clamp epilogue)
_GRAD = frozenset({"and", "lt"})
# masked multi-member select (sharded owners, quant/poly width groups)
_SELECT = frozenset({"gt", "select_n", "eq", "le", "iota", "squeeze", "and"})
# scalar-prefetch routed dispatch reads its fn_id row by grid position
_ROUTED = frozenset({"program_id"})
# RangeFold prologue/epilogue: Cody-Waite / Payne-Hanek octant bookkeeping
# (trig) and exponent-field bit splits (exp/log), fused in the kernel body
_FOLD = frozenset({
    "abs", "and", "bitcast_convert_type", "clz", "div", "eq", "gt",
    "is_finite", "lt", "ne", "neg", "not", "or", "rem", "round", "select_n",
    "shift_left", "shift_right_logical", "sign",
})

KERNEL_ALLOWED: Dict[str, frozenset] = {
    "_table_kernel": _BASE,
    "_table_grad_kernel": _BASE | _GRAD,
    "_pack_kernel": _BASE | _PLANE,
    "_pack_grad_kernel": _BASE | _PLANE | _GRAD,
    "_quant_kernel": _BASE,
    "_quant_grad_kernel": _BASE | _GRAD,
    "_poly_kernel": _BASE,
    "_poly_grad_kernel": _BASE | _GRAD,
    "_spack_kernel": _BASE | _SELECT,
    "_spack_grad_kernel": _BASE | _SELECT | _GRAD,
    "_folded_kernel": _BASE | _SELECT | _FOLD,
    "_folded_grad_kernel": _BASE | _SELECT | _FOLD | _GRAD,
    "_routed_kernel": _BASE | _SELECT | _ROUTED,
    "_routed_grad_kernel": _BASE | _SELECT | _ROUTED | _GRAD,
    "_routed_quant_kernel": _BASE | _SELECT | _ROUTED,
    "_routed_quant_grad_kernel": _BASE | _SELECT | _ROUTED | _GRAD,
    "_routed_poly_kernel": _BASE | _SELECT | _ROUTED,
    "_routed_poly_grad_kernel": _BASE | _SELECT | _ROUTED | _GRAD,
    # TableFlash: the fused exp_neg lookup flash attention calls in its
    # running-softmax step — _pack_kernel's body plus address saturation
    # (``max``, already in _BASE) and the underflow-to-zero tail select
    "_tableflash_kernel": _BASE | frozenset({"lt", "select_n"}),
}


# --------------------------------------------------------------------------------------
# The lint context: one cached build of every pack flavor + one cached trace
# per (mode, function, value|grad) closure, shared by all rules.
# --------------------------------------------------------------------------------------

class LintContext:
    """Shared pack builds, closures, and trace cache for one PackLint run."""

    def __init__(self, e_a: float = EA,
                 funcs: Optional[Sequence[str]] = None,
                 n_shards: int = N_SHARDS):
        self.e_a = float(e_a)
        self.funcs = tuple(funcs) if funcs is not None else tuple(function_names())
        if len(self.funcs) < 2:
            raise ValueError("PackLint needs >= 2 functions (reroute checks)")
        # folded modes read the canonical-interval core members of the pack
        cores = [c for n in self.funcs for c in FOLDABLE.get(n, ())
                 if c not in self.funcs]
        self.pack_names = self.funcs + tuple(dict.fromkeys(cores))
        self.n_shards = int(n_shards)
        self._cache: dict = {}

    # ---------------------------- pack builds -----------------------------

    def _memo(self, key, build):
        if key not in self._cache:
            self._cache[key] = build()
        return self._cache[key]

    def spec(self, name: str):
        return cached_table(name, self.e_a)

    def pack(self):
        return self._memo("pack", lambda: pack_specs(
            [self.spec(n) for n in self.pack_names]))

    def layout(self):
        return self._memo("layout", lambda: pack_layout(
            [self.spec(n) for n in self.pack_names]))

    def qpack(self):
        return self._memo("qpack", lambda: from_quant_layout(self.qlayout()))

    def qlayout(self):
        return self._memo("qlayout", lambda: quant_pack_layout(
            [plan_quant_member(n, self.e_a) for n in self.funcs]))

    def ppack(self):
        return self._memo("ppack", lambda: build_poly_pack(self.funcs, self.e_a))

    def pplan(self):
        # mirrors build_poly_pack's exact plan() call (rho=0.9, auto dtypes)
        return self._memo("pplan", lambda: design.plan(
            list(self.funcs), self.e_a, None, dtypes=design.POLY_DTYPES,
            algorithm="hierarchical", omega=0.3, rho=0.9))

    def spack(self):
        return self._memo("spack", lambda: shard_pack(self.layout(), self.n_shards))

    def slayout(self):
        return self._memo("slayout",
                          lambda: shard_pack_layout(self.layout(), self.n_shards))

    # ----------------------------- closures -------------------------------

    def x(self, name: str) -> np.ndarray:
        lo, hi = get_function(name).interval
        return np.linspace(lo, hi, N_GRID + 1)[:-1].astype(np.float32)

    def matrix(self, modes: Optional[Sequence[str]] = None
               ) -> Iterator[Tuple[str, str]]:
        from repro.approx.activations import _EXACT

        for m in (modes if modes is not None else ALL_MODES):
            for f in self.funcs:
                if m == "exact" and f not in _EXACT:
                    continue  # the canonical-interval core members are
                    # table-only: exact mode has no registered closure
                yield m, f

    def value_closure(self, mode: str, name: str) -> Callable:
        """``f(x)`` for one (mode, function) — the runtime the conformance
        matrix evaluates, as an un-evaluated closure (mirrors
        ``tests/test_conformance.approx_eval``)."""
        pk, rows = self.pack(), (lambda v: v.reshape(ROWS, -1))
        if mode == "exact":
            return get_exact(name)
        if mode == "table_ref":
            jt = from_spec(self.spec(name))
            return lambda v: eval_table_ref(jt, v)
        if mode == "table_pallas":
            jt = from_spec(self.spec(name))
            return lambda v: table_lookup_pallas(jt, v)
        if mode == "table_pack_ref":
            return lambda v: eval_pack_ref(pk, name, v)
        if mode == "table_pack":
            return lambda v: table_pack_lookup_pallas(pk, name, v)
        if mode == "quant_pack_ref":
            qp = self.qpack()
            return lambda v: eval_quant_pack_ref(qp, name, v)
        if mode == "quant_pack":
            qp = self.qpack()
            return lambda v: quant_pack_lookup_pallas(qp, name, v)
        if mode == "poly_pack_ref":
            pp = self.ppack()
            return lambda v: eval_poly_pack_ref(pp, name, v)
        if mode == "poly_pack":
            pp = self.ppack()
            return lambda v: poly_pack_lookup_pallas(pp, name, v)
        if mode == "routed_pack_ref":
            return lambda v: eval_routed_ref(pk, name, rows(v)).reshape(v.shape)
        if mode == "routed_pack":
            return lambda v: routed_pack_lookup_pallas(
                pk, name, rows(v)).reshape(v.shape)
        if mode == "routed_quant_pack_ref":
            qp = self.qpack()
            return lambda v: eval_routed_quant_ref(
                qp, name, rows(v)).reshape(v.shape)
        if mode == "routed_quant_pack":
            qp = self.qpack()
            return lambda v: routed_quant_pack_lookup_pallas(
                qp, name, rows(v)).reshape(v.shape)
        if mode == "routed_poly_pack_ref":
            pp = self.ppack()
            return lambda v: eval_routed_poly_ref(
                pp, name, rows(v)).reshape(v.shape)
        if mode == "routed_poly_pack":
            pp = self.ppack()
            return lambda v: routed_poly_pack_lookup_pallas(
                pp, name, rows(v)).reshape(v.shape)
        if mode == "sharded_pack_ref":
            sp = self.spack()
            return lambda v: eval_sharded_ref(sp, name, v)
        if mode == "sharded_pack":
            sp = self.spack()
            return lambda v: sharded_pack_lookup_pallas(sp, name, v)
        if mode == "folded_pack_ref":
            return lambda v: eval_folded_ref(pk, name, v)
        if mode == "folded_pack":
            return lambda v: folded_lookup(pk, name, v)
        if mode == "folded_routed_pack_ref":
            return lambda v: eval_folded_routed(pk, name, v, use_pallas=False)
        if mode == "folded_routed_pack":
            return lambda v: eval_folded_routed(pk, name, v, use_pallas=True)
        raise ValueError(f"unknown mode {mode!r}")  # pragma: no cover

    def unary_fn(self, mode: str, name: str) -> Callable:
        """The mode's differentiable unary (mirrors conformance
        ``approx_fn``)."""
        if mode == "exact":
            return get_exact(name)
        if mode in ("table_ref", "table_pallas"):
            return make_table_fn(from_spec(self.spec(name)),
                                 use_pallas=(mode == "table_pallas"))
        pallas = not mode.endswith("_ref")
        if mode in FOLDED_MODES:
            make = (make_folded_routed_unary_fn if "routed" in mode
                    else make_folded_fn)
            return make(self.pack(), name, use_pallas=pallas)
        if mode.startswith("routed"):
            pack = (self.ppack() if "poly" in mode
                    else self.qpack() if "quant" in mode else self.pack())
            return make_routed_unary_fn(pack, name, use_pallas=pallas)
        if mode.startswith("sharded"):
            return make_sharded_pack_fn(self.spack(), name, use_pallas=pallas)
        if mode.startswith("poly"):
            return make_poly_pack_fn(self.ppack(), name, use_pallas=pallas)
        if mode.startswith("quant"):
            return make_quant_pack_fn(self.qpack(), name, use_pallas=pallas)
        return make_pack_fn(self.pack(), name, use_pallas=pallas)

    def grad_closure(self, mode: str, name: str) -> Callable:
        fn = self.unary_fn(mode, name)
        return lambda v: jax.grad(lambda u: jnp.sum(fn(u)))(v)

    def traced(self, mode: str, name: str, kind: str):
        """Cached ClosedJaxpr of one (mode, function, value|grad) closure."""
        key = ("trace", mode, name, kind)

        def build():
            f = (self.value_closure(mode, name) if kind == "value"
                 else self.grad_closure(mode, name))
            return jl.trace(f, self.x(name))

        return self._memo(key, build)

    # ----------------------------- TableFlash -----------------------------

    def attn_x(self) -> np.ndarray:
        # flash attention feeds s - m_new <= 0; include a below-domain tail
        # so the clamp path is part of the traced closure
        return np.linspace(-20.0, 0.0, N_GRID).astype(np.float32)

    def attn_traced(self, kind: str):
        """Cached ClosedJaxpr of the TableFlash exp closure (value|grad)."""
        key = ("attn_trace", kind)

        def build():
            fn = make_attn_exp_fn(self.pack(), use_pallas=True)
            f = (fn if kind == "value"
                 else (lambda v: jax.grad(lambda u: jnp.sum(fn(u)))(v)))
            return jl.trace(f, self.attn_x())

        return self._memo(key, build)


# --------------------------------------------------------------------------------------
# Rule registry
# --------------------------------------------------------------------------------------

RULES: Dict[str, Callable[[LintContext], List[Finding]]] = {}


def rule(name: str):
    def deco(fn):
        RULES[name] = fn
        return fn
    return deco


def run(ctx: Optional[LintContext] = None,
        rules: Optional[Sequence[str]] = None) -> Report:
    """Run the registered rules and collect a :class:`Report`."""
    ctx = ctx or LintContext()
    names = list(rules) if rules is not None else list(RULES)
    rep = Report(meta={
        "e_a": ctx.e_a, "funcs": list(ctx.funcs),
        "modes": list(ALL_MODES), "n_shards": ctx.n_shards,
        "rules": names, "jax": jax.__version__,
    })
    for name in names:
        rep.extend(RULES[name](ctx))
    return rep


# --------------------------------------------------------------------------------------
# Rule 1 — f64 leakage
# --------------------------------------------------------------------------------------

@rule("f64_leak")
def rule_f64_leak(ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    artifacts = [("pack", ctx.pack()), ("quant_pack", ctx.qpack()),
                 ("poly_pack", ctx.ppack()), ("sharded_pack", ctx.spack())]
    for label, art in artifacts:
        hits = jl.array_leaf_wide_dtypes(art)
        out.append(Finding("f64_leak", f"artifact:{label}", not hits,
                           "; ".join(hits[:4])))
    for mode, name in ctx.matrix():
        for kind in ("value", "grad"):
            hits = jl.find_wide_dtypes(ctx.traced(mode, name, kind))
            out.append(Finding("f64_leak", f"{mode}/{name}/{kind}", not hits,
                               "; ".join(hits[:4])))
    return out


# --------------------------------------------------------------------------------------
# Rule 2 — forbidden primitives per kernel entry + callback-free closures
# --------------------------------------------------------------------------------------

def check_kernel(eqn, allowed: Optional[frozenset]) -> List[str]:
    """Violations of one lowered kernel body against its allowlist."""
    counts = jl.kernel_primitive_counts(eqn)
    bad = jl.forbidden_primitives(counts, allowed)
    bad += [f"dynamic-shape {d}" for d in jl.dynamic_shape_avals(jl.kernel_body(eqn))]
    return bad


@rule("kernel_primitives")
def rule_kernel_primitives(ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    seen_kernels = set()
    for mode, name in ctx.matrix():
        for kind in ("value", "grad"):
            traced = ctx.traced(mode, name, kind)
            # the closures here are built with observability off — the
            # runtime serving path — so ANY callback primitive is a leak
            cb = jl.closure_callbacks(traced)
            out.append(Finding(
                "kernel_primitives", f"closure:{mode}/{name}/{kind}", not cb,
                f"callback primitives on obs-off path: {cb}" if cb else ""))
            for eqn in jl.pallas_eqns(traced):
                kname = jl.kernel_name(eqn)
                if (kname, mode, kind) in seen_kernels:
                    continue  # one verdict per kernel flavor per mode/kind
                seen_kernels.add((kname, mode, kind))
                allowed = KERNEL_ALLOWED.get(kname)
                if allowed is None:
                    out.append(Finding(
                        "kernel_primitives", f"kernel:{kname}", False,
                        f"unregistered kernel entry (mode {mode}); add an "
                        f"allowlist row to analysis.contracts.KERNEL_ALLOWED"))
                    continue
                bad = check_kernel(eqn, allowed)
                out.append(Finding(
                    "kernel_primitives", f"kernel:{kname}[{mode}/{name}/{kind}]",
                    not bad, "; ".join(bad[:6])))
    # TableFlash: the attn_exp closure is its own runtime entry (a kernel the
    # mode matrix never launches) — same obs-off + allowlist contract
    if "exp_neg" in ctx.pack_names:
        for kind in ("value", "grad"):
            traced = ctx.attn_traced(kind)
            cb = jl.closure_callbacks(traced)
            out.append(Finding(
                "kernel_primitives", f"closure:attn_exp/{kind}", not cb,
                f"callback primitives on obs-off path: {cb}" if cb else ""))
            for eqn in jl.pallas_eqns(traced):
                kname = jl.kernel_name(eqn)
                allowed = KERNEL_ALLOWED.get(kname)
                if allowed is None:
                    out.append(Finding(
                        "kernel_primitives", f"kernel:{kname}", False,
                        "unregistered kernel entry (attn_exp); add an "
                        "allowlist row to analysis.contracts.KERNEL_ALLOWED"))
                    continue
                bad = check_kernel(eqn, allowed)
                out.append(Finding(
                    "kernel_primitives", f"kernel:{kname}[attn_exp/{kind}]",
                    not bad, "; ".join(bad[:6])))
    return out


# --------------------------------------------------------------------------------------
# Rule 3 — recompile hazards: routed reroutes + the serving tick
# --------------------------------------------------------------------------------------

# the module-global jitted dispatchers every routed entry point funnels into
_ROUTED_CALLEES = ("_routed_call", "_routed_quant_call", "_routed_poly_call",
                   "_sharded_routed_call")


def capture_routed_keys(entry: Callable, calls: Sequence[tuple]) -> Tuple[list, list]:
    """Invoke ``entry(*call)`` for each call with the module-global jitted
    routed dispatchers replaced by trace-only spies; returns (cache keys,
    weak-typed leaf paths).  ``jax.eval_shape`` through the real jitted
    callee keeps result shapes exact without executing a kernel."""
    import repro.kernels.routed_pack_lookup as rk

    keys, weak = [], []

    def make_spy(real):
        def spy(*args, **kw):
            keys.append(jl.jit_cache_key(args, static=kw))
            weak.extend(jl.weak_leaves(args))
            shapes = jax.eval_shape(functools.partial(real, **kw), *args)
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return spy

    saved = {n: getattr(rk, n) for n in _ROUTED_CALLEES}
    for n, real in saved.items():
        setattr(rk, n, make_spy(real))
    try:
        for call in calls:
            entry(*call)
    finally:
        for n, real in saved.items():
            setattr(rk, n, real)
    return keys, weak


def engine_stationarity_findings(batch: int = 2, cache_len: int = 32,
                                 prefill_len: int = 8) -> List[Finding]:
    """ContinuousEngine's two-executable invariant, proven on avals alone:
    abstract params (``jax.eval_shape(model.init, ...)``) + shape-only
    tracing of tick / prefill / refill-scatter — nothing runs."""
    from repro.models import ARCH_IDS, build_model, get_config
    from repro.serving.engine import (ContinuousEngine, cache_batch_axes,
                                      scatter_cache_slots)

    out: List[Finding] = []
    aid = next(a for a in ARCH_IDS if get_config(a).family == "dense")
    cfg = get_config(aid)
    period = max(1, cfg.attn.global_every)
    cfg = cfg.replace(d_model=64, vocab=128, remat=False, n_layers=2 * period,
                      n_heads=4, n_kv_heads=2, d_head=16, d_ff=128)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    eng = ContinuousEngine(model, params, batch, cache_len)
    out.append(Finding(
        "recompile_hazard", f"engine:{aid}:executables",
        set(eng._executables) == {"prefill", "decode_step"},
        f"executables={sorted(eng._executables)}"))

    cache = jax.eval_shape(lambda: model.init_cache(batch, cache_len))
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
    sig = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), cache)

    # tick stationarity: (nxt, logits, pos', cache') must re-feed tick
    # with byte-identical avals — one cache entry forever
    nxt, _, pos2, cache2 = jax.eval_shape(eng._tick, params, tok, pos, cache)
    sig2 = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), cache2)
    stationary = ((nxt.shape, str(nxt.dtype)) == (tok.shape, str(tok.dtype))
                  and (pos2.shape, str(pos2.dtype)) == (pos.shape, str(pos.dtype))
                  and sig2 == sig)
    out.append(Finding("recompile_hazard", f"engine:{aid}:tick-stationary",
                       stationary,
                       "" if stationary else
                       f"tick output avals drift: tok {nxt.shape}/{nxt.dtype}, "
                       f"pos {pos2.shape}/{pos2.dtype}"))
    tick_avals = jl.trace(eng._tick, params, tok, pos, cache).out_avals
    weak = [str(a) for a in tick_avals if getattr(a, "weak_type", False)]
    out.append(Finding("recompile_hazard", f"engine:{aid}:tick-weak-types",
                       not weak, f"weak-typed tick outputs: {weak[:4]}"))

    # one prefill executable: refill reuses the same (B, S0) signature and
    # must return a cache with the original avals (scatter target)
    toks = jax.ShapeDtypeStruct((batch, prefill_len), jnp.int32)
    _, pcache = jax.eval_shape(model.prefill, params, {"tokens": toks}, cache)
    psig = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), pcache)
    out.append(Finding("recompile_hazard", f"engine:{aid}:prefill-stationary",
                       psig == sig,
                       "" if psig == sig else "prefill cache avals drift"))

    axes = cache_batch_axes(model, cache_len)
    src = jax.eval_shape(lambda: model.init_cache(1, cache_len))
    scat = jax.eval_shape(lambda d, s: scatter_cache_slots(d, s, [0], axes),
                          cache, src)
    ssig = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), scat)
    out.append(Finding("recompile_hazard", f"engine:{aid}:refill-scatter",
                       ssig == sig,
                       "" if ssig == sig else "scattered cache avals drift"))
    return out


@rule("recompile_hazard")
def rule_recompile_hazard(ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    a, b = ctx.funcs[0], ctx.funcs[1]
    x2d = ctx.x(a).reshape(ROWS, -1)
    mixed = [a, b] * (ROWS // 2)
    variants = [
        ("routed_pack", routed_pack_lookup_pallas, ctx.pack),
        ("routed_pack.grad", routed_pack_grad_pallas, ctx.pack),
        ("routed_quant_pack", routed_quant_pack_lookup_pallas, ctx.qpack),
        ("routed_quant_pack.grad", routed_quant_pack_grad_pallas, ctx.qpack),
        ("routed_poly_pack", routed_poly_pack_lookup_pallas, ctx.ppack),
        ("routed_poly_pack.grad", routed_poly_pack_grad_pallas, ctx.ppack),
    ]
    for label, entry, packer in variants:
        pack = packer()
        keys, weak = capture_routed_keys(
            entry, [(pack, a, x2d), (pack, b, x2d), (pack, mixed, x2d)])
        ok = jl.keys_stable(keys) and len(keys) == 3
        out.append(Finding(
            "recompile_hazard", f"reroute:{label}", ok,
            "" if ok else f"{len(set(keys))} distinct jit cache keys over "
                          f"3 routings (expected 1)",
            {"n_calls": len(keys), "n_keys": len(set(keys))}))
        out.append(Finding("recompile_hazard", f"reroute:{label}:weak-types",
                           not weak, f"weak-typed operands: {weak[:4]}"))
    out.extend(engine_stationarity_findings())
    return out


# --------------------------------------------------------------------------------------
# Rule 4 — static VMEM accounting vs the planner's budgets
# --------------------------------------------------------------------------------------

def poly_lane_padding_allowance(plan) -> int:
    """The device PolyTablePack pads every member's zero/ramp/scale planes to
    the pack-wide max lane count; ``PackPlan.vmem()`` prices each member's own
    lanes.  The delta is a documented allowance, not a budget change —
    changing ``vmem()`` itself would shift the CI-gated BENCH_polypack
    numbers."""
    lmax = max(m.lanes for m in plan.members)
    return 3 * 4 * sum((lmax - m.lanes) * m.n_intervals for m in plan.members)


def routed_dispatch_allowance(plan) -> int:
    """Static kernels bake each member's interval count into the executable
    (a static arg); the routed kernel dispatches on fn_id at runtime, so it
    additionally pins the per-interval ``seg_count`` plane — one f32 lane per
    interval.  Priced here as a documented allowance on top of
    ``PackPlan.vmem()`` rather than folded into the planner (which budgets
    the static pack)."""
    return 4 * sum(m.n_intervals for m in plan.members)


def check_budget(resident: int, budget: int, subject: str,
                 allowance: int = 0) -> Finding:
    ok = 0 < resident <= budget + allowance
    return Finding(
        "vmem_budget", subject, ok,
        "" if ok else f"kernel pins {resident} B of pack operands but the "
                      f"planner budget is {budget} B (+{allowance} B allowance)",
        {"resident_bytes": resident, "budget_bytes": budget,
         "allowance_bytes": allowance})


@rule("vmem_budget")
def rule_vmem_budget(ctx: LintContext) -> List[Finding]:
    budgets = {
        "table_pack": (lambda: ctx.layout().vmem().padded_bytes, 0),
        "quant_pack": (lambda: ctx.qlayout().vmem().padded_bytes, 0),
        "poly_pack": (lambda: ctx.pplan().vmem().padded_bytes,
                      poly_lane_padding_allowance(ctx.pplan())),
        "sharded_pack": (lambda: ctx.slayout().vmem().padded_bytes, 0),
    }

    def family(mode: str) -> str:
        if "poly" in mode:
            return "poly_pack"
        if "quant" in mode:
            return "quant_pack"
        if mode.startswith("sharded"):
            return "sharded_pack"
        return "table_pack"

    out: List[Finding] = []
    for mode, name in ctx.matrix(modes=TABLE_MODES):
        if mode.endswith("_ref") or mode in ("table_ref", "table_pallas"):
            continue
        budget_fn, allowance = budgets[family(mode)]
        budget = budget_fn()
        if mode.startswith("routed") and family(mode) == "poly_pack":
            allowance += routed_dispatch_allowance(ctx.pplan())
        for kind in ("value", "grad"):
            eqns = jl.pallas_eqns(ctx.traced(mode, name, kind))
            if not eqns:
                out.append(Finding("vmem_budget", f"{mode}/{name}/{kind}",
                                   False, "no pallas_call in a pallas mode"))
                continue
            for i, eqn in enumerate(eqns):
                # sharded modes launch one kernel per shard; each launch must
                # fit the PER-SHARD budget independently
                suffix = f"[{i}]" if len(eqns) > 1 else ""
                out.append(check_budget(
                    jl.pack_resident_bytes(eqn), budget,
                    f"{mode}/{name}/{kind}{suffix}", allowance))
    # TableFlash pins the same full-pack planes as _pack_kernel, so it is
    # priced against the same PackLayout budget
    if "exp_neg" in ctx.pack_names:
        budget = ctx.layout().vmem().padded_bytes
        for kind in ("value", "grad"):
            eqns = jl.pallas_eqns(ctx.attn_traced(kind))
            if not eqns:
                out.append(Finding("vmem_budget", f"attn_exp/{kind}", False,
                                   "no pallas_call in the attn_exp closure"))
                continue
            for eqn in eqns:
                out.append(check_budget(jl.pack_resident_bytes(eqn), budget,
                                        f"attn_exp/{kind}"))
    return out


# --------------------------------------------------------------------------------------
# Rule 5 — obs-off structural identity
# --------------------------------------------------------------------------------------

def obs_identity_fingerprints(build: Callable[[], Callable], x) -> Tuple[str, str]:
    """(obs-never, obs-enabled-telemetry-off) fingerprints of one closure
    builder; process obs state is restored afterwards."""
    from repro.obs import config as obs_config

    old = obs_config.get_config()
    try:
        obs.disable()
        fp_never = jl.fingerprint(build(), x)
        obs.configure(enabled=True, device_telemetry=False)
        fp_disabled = jl.fingerprint(build(), x)
    finally:
        obs.configure(enabled=old.enabled,
                      device_telemetry=old.device_telemetry,
                      trace_path=old.trace_path)
    return fp_never, fp_disabled


@rule("obs_off_identity")
def rule_obs_off_identity(ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    foldable = [n for n in ctx.funcs if n in FOLDABLE]
    for mode in ALL_MODES:
        # pick a member the mode can serve: folded modes exercise the fold
        # path only on foldable names
        name = (foldable[0] if mode in FOLDED_MODES and foldable
                else ("tanh" if "tanh" in ctx.funcs else ctx.funcs[0]))
        cfg_kw = dict(mode=mode, e_a=ctx.e_a, pack_functions=ctx.pack_names,
                      pack_shards=ctx.n_shards)
        fp_never, fp_disabled = obs_identity_fingerprints(
            lambda: ApproxConfig(**cfg_kw).unary(name), ctx.x(name))
        ok = fp_never == fp_disabled
        out.append(Finding(
            "obs_off_identity", f"unary:{mode}/{name}", ok,
            "" if ok else "obs-on(disabled) closure is structurally different "
                          "from the obs-never closure (zero-overhead contract)"))
    # the routed dispatch API has its own instrumentation wrapper
    fns = [ctx.funcs[0], ctx.funcs[1]] * (ROWS // 2)
    xr = ctx.x(ctx.funcs[0]).reshape(ROWS, -1)
    fp_never, fp_disabled = obs_identity_fingerprints(
        lambda: ApproxConfig(mode="routed_pack", e_a=ctx.e_a,
                             pack_functions=ctx.pack_names).routed_fn(fns), xr)
    out.append(Finding("obs_off_identity", "routed_fn:routed_pack",
                       fp_never == fp_disabled,
                       "" if fp_never == fp_disabled else
                       "routed_fn obs-off closure differs structurally"))
    # TableFlash's attn_exp has its own telemetry wrapper (approx.oob counter
    # + count_mask protocol) — with telemetry off it must vanish structurally
    if "exp_neg" in ctx.pack_names:
        fp_never, fp_disabled = obs_identity_fingerprints(
            lambda: ApproxConfig(mode="table_pack", e_a=ctx.e_a,
                                 pack_functions=ctx.pack_names,
                                 attn_table=True).attn_exp(), ctx.attn_x())
        out.append(Finding("obs_off_identity", "attn_exp:table_pack",
                           fp_never == fp_disabled,
                           "" if fp_never == fp_disabled else
                           "attn_exp obs-off closure differs structurally"))
    return out
