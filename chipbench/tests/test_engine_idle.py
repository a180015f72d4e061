"""Device-idle time put down to engine spans: on events built by hand, on the
trace recorded on a TPU v5e, and on a tiny serve profiled on the CPU."""

import statistics
from pathlib import Path

import pytest

import devtrace
import engine_idle

P = "/device:TPU:0"
# window [100, 300); the device runs [100, 120), [150, 160), [200, 260)
TR = {
    "window": [100, 200],
    "ops": {P: [["%fusion.1", 100, 20], ["%fusion.2", 150, 10],
                ["%fusion.3", 200, 60]]},
    "modules": {P: [["jit_prefill(1)", 100, 20], ["jit_tick(2)", 150, 10],
                    ["jit_tick(2)", 200, 60]]},
    "host": [["bench.serve", 100, 190]],
    "engine": [["engine.serve", 105, 180],
               ["engine.admit", 105, 40],
               ["engine.prefill", 105, 5],
               ["engine.prefill_wait", 112, 20],
               ["engine.first_tokens", 132, 13],
               ["engine.decode", 145, 135],
               ["engine.dispatch", 145, 10],
               ["engine.settle", 155, 100],
               ["engine.emit", 255, 25]],
}
TR["host"] = TR["host"] + TR["engine"]


def test_innermost_tiles_the_window():
    assert engine_idle.innermost(TR["engine"], 100, 300) == [
        [100, 105, None], [105, 110, "engine.prefill"],
        [110, 112, "engine.admit"], [112, 132, "engine.prefill_wait"],
        [132, 145, "engine.first_tokens"], [145, 155, "engine.dispatch"],
        [155, 255, "engine.settle"], [255, 280, "engine.emit"],
        [280, 285, "engine.serve"], [285, 300, None]]


def test_each_idle_instant_goes_to_its_innermost_span():
    by = engine_idle.idle_by_span(TR)
    ns = {k: round(v * 1e9) for k, v in by.items()}
    # idle: [120, 150), [160, 200), [260, 300)
    assert ns == {"engine.prefill_wait": 12, "engine.first_tokens": 13,
                  "engine.dispatch": 5, "engine.settle": 40,
                  "engine.emit": 20, "engine.serve": 5, None: 15}


def test_split_adds_up_to_device_idle():
    s = engine_idle.split(TR)
    assert s["wait"] == pytest.approx(52e-9)
    assert s["host_bound"] == pytest.approx(43e-9)
    assert s["outside"] == pytest.approx(15e-9)
    idle_frac = 1 - devtrace.busy_s(TR) / devtrace.window_s(TR)
    assert (s["wait"] + s["host_bound"] + s["outside"]) \
        / devtrace.window_s(TR) == pytest.approx(idle_frac)


def test_idle_gaps_name_the_engine_phase():
    # each gap takes the innermost span around its middle: 180, 280, 135
    assert devtrace.idle_gaps(TR) == [["engine.settle", 40e-9],
                                      ["engine.serve", 40e-9],
                                      ["engine.first_tokens", 30e-9]]


RECORDED = devtrace.read_saved(str(Path(__file__).parent / "data" /
                                   "v5e_stablelm_queue_start.json.gz"))


def test_recorded_trace_reads_as_before():
    """Engine spans added to the recorded v5e trace change no device
    number, and its idle time splits into the three shares exactly."""
    before = (devtrace.busy_s(RECORDED), devtrace.window_s(RECORDED),
              devtrace.module_s(RECORDED, "jit_prefill"),
              devtrace.module_s(RECORDED, "jit_tick"),
              devtrace.op_s(RECORDED, devtrace.pack_kernel),
              devtrace.top_ops(RECORDED))
    assert engine_idle.split(dict(RECORDED, engine=[])) is None
    _, lo, dur = next(h for h in RECORDED["host"] if h[0] == "bench.serve")
    mods = sorted(RECORDED["modules"][P], key=lambda e: e[1])
    # engine.serve inside the harness's bench.serve, and one engine.decode
    # span from each tick's start to the next module's, a settle inside it
    engine = [["engine.serve", lo + 1, dur - 2]]
    for m, nxt in zip(mods, mods[1:]):
        if m[0].startswith("jit_tick("):
            engine.append(["engine.decode", m[1], nxt[1] - m[1]])
            engine.append(["engine.settle", m[1] + 1, nxt[1] - m[1] - 2])
    tr = dict(RECORDED, engine=engine, host=RECORDED["host"] + engine)
    after = (devtrace.busy_s(tr), devtrace.window_s(tr),
             devtrace.module_s(tr, "jit_prefill"),
             devtrace.module_s(tr, "jit_tick"),
             devtrace.op_s(tr, devtrace.pack_kernel), devtrace.top_ops(tr))
    assert after == before
    s = engine_idle.split(tr)
    idle = devtrace.window_s(tr) - devtrace.busy_s(tr)
    assert s["wait"] + s["host_bound"] + s["outside"] == \
        pytest.approx(idle, abs=1e-9)
    assert min(s["wait"], s["host_bound"], s["outside"]) > 0
    labels = {g[0] for g in devtrace.idle_gaps(tr)}
    assert "bench.serve" not in labels
    assert labels <= {"engine.serve", "engine.decode", "engine.settle",
                      "bench.queue_build"}


def test_profiled_serve_has_bare_nested_engine_spans(tmp_path):
    """A tiny serve profiled on the CPU: the engine's annotations reach the
    profiler's host plane under their bare names, nested as documented."""
    import jax
    import numpy as np

    from repro.models import build_model
    from repro.serving.engine import ENGINE_SPANS, ContinuousEngine, Request
    from conftest import tiny_cell

    cell = tiny_cell("stablelm-3b")
    model = build_model(cell["family"].program_config(cell["config"]))
    eng = ContinuousEngine(model, model.init(jax.random.key(0)), 2, 32)
    reqs = [Request(prompt=np.arange(1, 4 + i, dtype=np.int32),
                    max_new_tokens=2 + i) for i in range(4)]
    eng.serve(reqs)  # warm
    with devtrace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            eng.serve(reqs)
    tr = engine_idle.load(str(tmp_path))
    names = [e[0] for e in tr["engine"]]
    assert set(names) == set(ENGINE_SPANS)
    assert names.count("engine.serve") == 1
    lo, dur = tr["window"]
    serve = next(e for e in tr["engine"] if e[0] == "engine.serve")
    assert lo <= serve[1] and serve[1] + serve[2] <= lo + dur
    for name, s, d in tr["engine"]:
        parent = ENGINE_SPANS[name]
        if parent is not None:
            assert any(p == parent and ps <= s and s + d <= ps + pd
                       for p, ps, pd in tr["engine"]), name
    assert statistics.mean(d for _, _, d in tr["engine"]) > 0


def test_profile_reports_a_window(cell):
    """``profile`` drives one whole window of a tiny cell on the CPU: the
    engine spans are found; with no device plane there is no split."""
    import bench

    served = bench.build(cell, 3)
    out = engine_idle.profile(served, cell, 4100000001, 0.0, 0.5)
    assert out["queues"] == 1 and out["engine_spans"] > 0
    assert out["split"] is None and out["gaps"] == []
    assert out["output_tokens_per_s"] > 0
