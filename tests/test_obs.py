"""ScopeKit (repro.obs): trace schema, metrics, report, and the overhead
contract.

The load-bearing guarantees pinned here:

* **Deterministic trace structure** — two identical greedy mixed-EOS queues
  through a warm ContinuousEngine record the SAME ``(name, ph, tid)`` event
  sequence (timestamps differ, structure may not), and every trace passes
  ``tools/check_trace.py``'s validator (balanced/nested B/E per track,
  non-decreasing timestamps, known phases).
* **Zero-cost off, zero-recompile on** — with ObsConfig disabled nothing is
  recorded; flipping host-side recording on between serves of the SAME engine
  adds no compiled executables (``compile_counts`` unchanged) and leaves the
  tokens bit-identical.
* **Device telemetry** — out-of-domain clamp counts, quant-code saturation,
  and routed dispatch land in the global registry when (and only when) the
  activation closures were built with ``device_telemetry`` on.
* ``engine.reset_counters()`` resets the engine's metric registry along with
  the batch/wasted-step integers.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.approx import ApproxConfig
from repro.models import build_model
from repro.obs.report import diff_summaries, render_summary, span_stats
from repro.serving.engine import (
    ENGINE_SPANS,
    WAIT_SPANS,
    ContinuousEngine,
    DecodeEngine,
)

from tests.test_archs import reduced
from tests.test_serving import mixed_requests

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from check_trace import validate_trace  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with ScopeKit fully off and empty."""
    obs.disable()
    obs.reset_tracer()
    obs.reset_registry()
    yield
    obs.disable()
    obs.reset_tracer()
    obs.reset_registry()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced("stablelm-3b").replace(n_layers=2)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def fixed_queue():
    """The pinned mixed-length mixed-EOS queue the schema test serves."""
    return mixed_requests(np.random.default_rng(7), 6)


# --------------------------------------------------------------------------------------
# metrics layer
# --------------------------------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_histogram(self):
        r = obs.Registry()
        r.counter("c").add()
        r.counter("c").add(4)
        r.gauge("g").set(2.5)
        for v in range(100):
            r.histogram("h").observe(float(v))
        s = r.summary()
        assert s["counters"]["c"] == 5
        assert s["gauges"]["g"] == 2.5
        h = s["histograms"]["h"]
        assert h["count"] == 100 and h["min"] == 0.0 and h["max"] == 99.0
        assert h["p50"] == pytest.approx(49.5)
        assert h["p99"] == pytest.approx(98.01)

    def test_registry_reset_and_global(self):
        obs.get_registry().counter("x").add(3)
        assert obs.get_registry().summary()["counters"]["x"] == 3
        obs.reset_registry()
        assert obs.get_registry().summary()["counters"] == {}

    def test_percentiles_empty(self):
        assert obs.percentiles([]) == {}

    def test_histogram_decimation_keeps_percentiles(self):
        from repro.obs import metrics as M
        h = M.Histogram()
        n = M.HIST_CAP + M.HIST_CAP // 2
        for v in range(n):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == n
        assert len(h.values) < M.HIST_CAP
        # decimated percentiles stay within ~1% of the exact uniform answer
        assert s["p50"] == pytest.approx(0.5 * n, rel=0.02)


# --------------------------------------------------------------------------------------
# tracer invariants
# --------------------------------------------------------------------------------------


class TestTracer:
    def test_span_balanced_and_valid(self, tmp_path):
        tr = obs.Tracer()
        with tr.span("outer", "t") as s:
            with tr.span("inner", "t"):
                tr.instant("tick", "t")
            s["extra"] = 1
        tr.counter("gauge", {"a": 1, "b": 2})
        doc = tr.to_json(metadata={"k": "v"})
        assert validate_trace(doc) == []
        path = tr.save(str(tmp_path / "t.json"))
        with open(path) as f:
            assert validate_trace(json.load(f)) == []
        ends = [e for e in doc["traceEvents"] if e.get("ph") == "E"]
        assert ends[-1]["args"] == {"extra": 1}  # end_args land on the E

    def test_module_helpers_noop_when_disabled(self):
        tr = obs.reset_tracer()
        n0 = len(tr.events)
        with obs.span("nope"):
            obs.instant("nope")
            obs.counter_event("nope", 1)
        assert len(tr.events) == n0
        obs.configure(enabled=True)
        with obs.span("yes"):
            pass
        assert len(tr.events) == n0 + 2

    def test_traced_decorator_fires_on_lru_miss_only(self):
        from functools import lru_cache

        @lru_cache(maxsize=8)
        @obs.traced("phase.x", "design")
        def work(a):
            return a * 2

        obs.configure(enabled=True)
        tr = obs.reset_tracer()
        assert work(3) == 6 and work(3) == 6 and work(4) == 8
        spans = [e for e in tr.events if e["name"] == "phase.x"
                 and e["ph"] == "B"]
        assert len(spans) == 2  # two misses, one hit

    def test_validator_catches_violations(self):
        bad = {"traceEvents": [
            {"name": "a", "ph": "B", "ts": 2.0, "pid": 1, "tid": 0},
            {"name": "b", "ph": "E", "ts": 1.0, "pid": 1, "tid": 0},
            {"name": "c", "ph": "Z", "ts": 3.0, "pid": 1, "tid": 0},
        ]}
        errs = validate_trace(bad)
        assert any("not nested" in e for e in errs)
        assert any("backwards" in e for e in errs)
        assert any("unknown phase" in e for e in errs)


# --------------------------------------------------------------------------------------
# report layer
# --------------------------------------------------------------------------------------


def _mini_doc(scale=1.0):
    evs = []
    t = 0.0
    for _ in range(3):
        evs.append({"name": "work", "ph": "B", "ts": t, "pid": 1, "tid": 0})
        evs.append({"name": "work", "ph": "E", "ts": t + 100.0 * scale,
                    "pid": 1, "tid": 0})
        t += 200.0 * scale
    return {"traceEvents": evs,
            "metadata": {"metrics": {"histograms": {
                "ttft_s": {"count": 3, "p50": 0.01 * scale,
                           "p95": 0.02 * scale, "p99": 0.03 * scale}}}}}


class TestReport:
    def test_span_stats(self):
        s = span_stats(_mini_doc())
        assert s["work"]["count"] == 3
        assert s["work"]["total_us"] == pytest.approx(300.0)
        assert s["work"]["mean_us"] == pytest.approx(100.0)

    def test_render_and_diff(self):
        text = render_summary(_mini_doc(), "run")
        assert "work" in text and "ttft_s" in text
        d = diff_summaries(_mini_doc(1.0), _mini_doc(2.0))
        assert "+100.0%" in d


# --------------------------------------------------------------------------------------
# engine traces: schema, determinism, overhead contract
# --------------------------------------------------------------------------------------


def _serve_traced(engine, reqs):
    obs.configure(enabled=True)
    tr = obs.reset_tracer()
    results = engine.serve(reqs)
    obs.configure(enabled=False)
    return results, tr.to_json(metadata={"metrics": engine.metrics.summary()})


class TestEngineTraces:
    def test_continuous_trace_schema(self, tiny_model):
        """A mixed-EOS continuous serve produces a validator-clean trace with
        the documented span taxonomy and a balanced per-slot request track."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        results, doc = _serve_traced(eng, fixed_queue())
        assert all(r is not None for r in results)
        assert validate_trace(doc) == []
        evs = doc["traceEvents"]
        names = {e["name"] for e in evs}
        assert {"request", "first_token", "slots_occupied",
                *ENGINE_SPANS} <= names
        # one balanced request B/E pair per served request, on slot tracks
        req_b = [e for e in evs if e["name"] == "request" and e["ph"] == "B"]
        req_e = [e for e in evs if e["name"] == "request" and e["ph"] == "E"]
        assert len(req_b) == len(results) == len(req_e)
        from repro.obs.trace import SLOT_TID0
        assert all(e["tid"] >= SLOT_TID0 for e in req_b)
        assert {e["args"]["req_idx"] for e in req_b} == set(range(len(results)))
        # E carries the per-request token count
        by_tid = {}
        for e in evs:
            if e["name"] == "request":
                by_tid.setdefault(e["tid"], []).append(e)
        for seq in by_tid.values():
            for b, e in zip(seq[0::2], seq[1::2]):
                assert (b["ph"], e["ph"]) == ("B", "E")
        # metrics made it into the embedded summary
        hists = doc["metadata"]["metrics"]["histograms"]
        assert hists["ttft_s"]["count"] == len(results)
        assert hists["queue_wait_s"]["count"] == len(results)

    def test_trace_structure_deterministic(self, tiny_model):
        """Two identical warm serves record identical (name, ph, tid)
        sequences — the schema test's stability guarantee."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        eng.serve(fixed_queue())  # warm: compile outside the compared runs
        _, doc_a = _serve_traced(eng, fixed_queue())
        _, doc_b = _serve_traced(eng, fixed_queue())

        def structure(doc):
            return [(e["name"], e["ph"], e["tid"])
                    for e in doc["traceEvents"]]

        assert structure(doc_a) == structure(doc_b)
        # and timestamps are strictly usable: non-decreasing overall clock
        ts = [e["ts"] for e in doc_a["traceEvents"] if "ts" in e]
        assert all(b >= a for a, b in zip(ts, ts[1:]))

    def test_obs_adds_no_recompiles_and_keeps_tokens(self, tiny_model):
        """Flipping host-side recording on between serves of the same engine
        adds ZERO compiled executables and leaves greedy tokens identical."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        base = eng.serve(fixed_queue())
        counts_off = eng.compile_counts()
        obs.configure(enabled=True)
        traced = eng.serve(fixed_queue())
        obs.configure(enabled=False)
        assert eng.compile_counts() == counts_off
        for a, b in zip(base, traced):
            assert np.array_equal(a.tokens, b.tokens)

    @pytest.mark.parametrize("obs_on", [False, True], ids=["obs_off", "obs_on"])
    def test_serve_never_blocks(self, tiny_model, monkeypatch, obs_on):
        """The engine's spans add no sync: ContinuousEngine.serve calls
        ``jax.block_until_ready`` nowhere, with ScopeKit on or off."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        eng.serve(fixed_queue())  # warm
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: calls.append(1) or real(x))
        obs.configure(enabled=obs_on)
        results = eng.serve(fixed_queue())
        obs.configure(enabled=False)
        assert all(r is not None for r in results)
        assert calls == []

    def test_serve_annotations_follow_taxonomy(self, tiny_model, monkeypatch):
        """The profiler spans one serve opens are the documented engine
        phases, each inside its documented parent, properly nested, the same
        with ScopeKit on and off; and with ScopeKit on its engine track
        carries the same spans in the same order."""
        model, params = tiny_model
        eng = ContinuousEngine(model, params, batch_size=2, cache_len=32)
        base = eng.serve(fixed_queue())  # warm
        counts = eng.compile_counts()
        log = []

        class Recorder:
            def __init__(self, name, **args):
                self.name, self.args = name, args

            def __enter__(self):
                log.append(("B", self.name, self.args))

            def __exit__(self, *exc):
                log.append(("E", self.name, {}))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
        seen = {}
        for obs_on in (False, True):
            del log[:]
            obs.configure(enabled=obs_on)
            tr = obs.reset_tracer()
            results = eng.serve(fixed_queue())
            obs.configure(enabled=False)
            for a, b in zip(base, results):
                assert np.array_equal(a.tokens, b.tokens)
            seen[obs_on] = list(log)
            if obs_on:
                track = [(e["ph"], e["name"]) for e in tr.events
                         if e.get("tid") == 0 and e["ph"] in "BE"]
                assert track == [(ph, n) for ph, n, _ in log]
        assert eng.compile_counts() == counts
        assert seen[False] == seen[True]
        stack = []
        for ph, name, args in seen[False]:
            if ph == "B":
                assert name in ENGINE_SPANS
                assert ENGINE_SPANS[name] == (stack[-1] if stack else None)
                stack.append(name)
            else:
                assert stack.pop() == name
        assert stack == []
        opened = [(n, a) for ph, n, a in seen[False] if ph == "B"]
        assert {n for n, _ in opened} == set(ENGINE_SPANS)
        assert set(WAIT_SPANS) < set(ENGINE_SPANS)
        width = max(len(r.prompt) for r in fixed_queue())
        assert opened[0] == ("engine.serve", {"requests": 6, "batch": 2,
                                              "prefill_len": width})
        assert all(set(a) == {"k", "slots"} for n, a in opened
                   if n == "engine.decode")

    def test_static_engine_records_latency(self, tiny_model):
        model, params = tiny_model
        eng = DecodeEngine(model, params, batch_size=2, cache_len=32)
        obs.configure(enabled=True)
        obs.reset_tracer()
        prompts = np.ones((2, 4), np.int32)
        eng.generate_batch(prompts, max_new=5)
        obs.configure(enabled=False)
        hists = eng.metrics.summary()["histograms"]
        assert hists["ttft_s"]["count"] == 1
        assert hists["itl_s"]["count"] == 4  # 5 tokens -> 4 intervals
        names = {e["name"] for e in obs.get_tracer().events}
        assert {"static.prefill", "static.decode"} <= names

    def test_reset_counters_resets_metrics(self, tiny_model):
        model, params = tiny_model
        eng = DecodeEngine(model, params, batch_size=2, cache_len=32)
        obs.configure(enabled=True)
        eng.generate_batch(np.ones((2, 4), np.int32), max_new=3)
        obs.configure(enabled=False)
        assert eng.metrics.summary()["histograms"]
        assert eng.compile_time_s > 0.0
        eng.reset_counters()
        assert eng.metrics.summary()["histograms"] == {}
        assert eng.compile_time_s == 0.0
        assert eng.batch_steps == 0 and eng.wasted_slot_steps == 0


# --------------------------------------------------------------------------------------
# device telemetry
# --------------------------------------------------------------------------------------


class TestDeviceTelemetry:
    def test_oob_and_saturation_counters(self):
        obs.configure(enabled=True, device_telemetry=True)
        cfg = ApproxConfig(mode="quant_pack_ref", e_a=1e-3)
        f = jax.jit(cfg.unary("tanh"))
        # tanh's table spans [lo, 0); the odd extension serves (lo, -lo) —
        # half this probe sits beyond it on each side
        x = jnp.asarray(np.linspace(-16, 16, 64, dtype=np.float32))
        f(x)
        jax.effects_barrier()
        c = obs.get_registry().summary()["counters"]
        assert c["approx.lookups.tanh"] == 64
        assert 0 < c["approx.oob.tanh"] < 64
        assert c["approx.quant_gathers.tanh"] == 128
        assert 0 <= c["approx.quant_sat.tanh"] <= 128

    def test_routed_dispatch_histogram(self):
        obs.configure(enabled=True, device_telemetry=True)
        cfg = ApproxConfig(mode="routed_pack_ref", e_a=1e-3)
        g = jax.jit(cfg.routed_fn(["gelu", "tanh", "gelu"]))
        for _ in range(2):
            g(jnp.ones((3, 8), jnp.float32))
        jax.effects_barrier()
        c = obs.get_registry().summary()["counters"]
        assert c["approx.routed.gelu"] == 4  # 2 rows x 2 executions
        assert c["approx.routed.tanh"] == 2

    def test_off_by_default_records_nothing(self):
        cfg = ApproxConfig(mode="quant_pack_ref", e_a=1e-3)
        f = jax.jit(cfg.unary("tanh"))
        f(jnp.asarray(np.linspace(-4, 4, 32, dtype=np.float32)))
        jax.effects_barrier()
        assert obs.get_registry().summary()["counters"] == {}

    def test_enable_after_build_has_no_effect(self):
        """The build-time contract: closures built before the flag flips stay
        uninstrumented (documented in ObsConfig)."""
        cfg = ApproxConfig(mode="quant_pack_ref", e_a=1e-3)
        f = jax.jit(cfg.unary("tanh"))
        obs.configure(enabled=True, device_telemetry=True)
        f(jnp.asarray(np.linspace(-4, 4, 32, dtype=np.float32)))
        jax.effects_barrier()
        assert obs.get_registry().summary()["counters"] == {}
