"""The reduction from trace events to numbers, on events built by hand, and
on a trace recorded on a TPU v5e."""

from pathlib import Path

import devtrace

P = "/device:TPU:0"
KERNEL = "%_call.9[tpu_custom_call]"
TR = {
    "window": [100, 100],  # [start ns, duration ns]
    "ops": {P: [["%while.1", 90, 30],      # clipped to [100, 120), a loop
                [KERNEL, 100, 10],         # in the loop's body
                ["%fusion.2", 112, 5],     # in the loop's body
                ["%fusion.2", 130, 5],
                ["%copy.3", 190, 30]]},    # clipped to [190, 200)
    "modules": {P: [["jit_prefill(1)", 90, 30], ["jit_tick(7)", 130, 5],
                    ["jit_tick(7)", 190, 10]]},
    "host": [["bench.serve", 100, 100], ["bench.results", 150, 20]],
}


def test_busy_and_window():
    assert devtrace.busy_intervals(TR, P) == [[100, 120], [130, 135],
                                              [190, 200]]
    assert devtrace.busy_s(TR) == 35e-9
    assert devtrace.window_s(TR) == 100e-9


def test_named_times():
    assert devtrace.module_s(TR, "jit_prefill") == 20e-9
    assert devtrace.module_s(TR, "jit_tick") == 15e-9
    assert devtrace.op_s(TR, lambda n: n == KERNEL) == 10e-9


def test_top_ops_count_leaves_by_module():
    assert devtrace.top_ops(TR) == [[f"jit_prefill/{KERNEL}", 10e-9],
                                    ["jit_tick/%copy.3", 10e-9],
                                    ["jit_prefill/%fusion.2", 5e-9],
                                    ["jit_tick/%fusion.2", 5e-9]]


def test_idle_gaps_take_the_innermost_span():
    # gaps [135, 190) then [120, 130); the first's middle lies in
    # bench.results, inside bench.serve
    assert devtrace.idle_gaps(TR) == [["bench.results", 55e-9],
                                      ["bench.serve", 10e-9]]


def test_op_names():
    assert devtrace.op_name("%fusion.140 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "%fusion.140"
    assert devtrace.op_name('%_call.9 = bf16[8,512]{1,0} custom-call(%x), '
                            'custom_call_target="tpu_custom_call"') == KERNEL
    assert devtrace.op_name("jit_tick(123)") == "jit_tick(123)"


RECORDED = devtrace.read_saved(str(Path(__file__).parent / "data" /
                                   "v5e_stablelm_queue_start.json.gz"))
LAYERS = 9  # of the recorded run (its "recorded" note)


def test_recorded_kernel_runs_once_per_layer_and_executable():
    kernel = devtrace.pack_kernel
    ops = RECORDED["ops"][P]
    for name, s, d in RECORDED["modules"][P]:
        if name.startswith(("jit_prefill(", "jit_tick(")):
            inside = [o for o in ops if kernel(o[0]) and s <= o[1]
                      and o[1] + o[2] <= s + d]
            assert len(inside) == LAYERS, name


def test_recorded_busy_matches_a_timeline():
    lo, dur = RECORDED["window"]
    us = [False] * (dur // 1000 + 1)
    for _, s, d in RECORDED["ops"][P]:
        for t in range(max(s, lo) - lo, min(s + d, lo + dur) - lo, 1000):
            us[t // 1000] = True
    assert abs(devtrace.busy_s(RECORDED) - sum(us) * 1e-6) < 1e-3
    busy = devtrace.busy_s(RECORDED)
    assert devtrace.module_s(RECORDED, "jit_prefill") \
        + devtrace.module_s(RECORDED, "jit_tick") <= busy <= \
        devtrace.window_s(RECORDED)
    leaves = sum(t for _, t in devtrace.top_ops(RECORDED, 10**6))
    assert leaves <= busy + 1e-9
