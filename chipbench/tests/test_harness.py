"""A whole run at a tiny size on the CPU, sound and with the timed path
broken underneath; the files a cell is found by; the run without a chip."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import bench

REPO = Path(__file__).resolve().parents[2]
SEED = 2**33 + 77  # wider than 32 bits, as the driver's seeds are


def run(cell, chip, seconds=0.5, trace=False):
    return bench.run_cell(cell, SEED, seconds, trace, chip, time.perf_counter())


def test_sound_run_is_correct(cell, cpu_chip):
    r = run(cell, cpu_chip)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"logit_gap", "logit_err", "failed_requests"}
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


def test_traced_run_is_correct(cell, cpu_chip):
    r = run(cell, cpu_chip, trace=True)
    assert r["correct"] and "breakdown" in r and "window_s" in r["device"]


def test_altered_token_is_caught(cell, cpu_chip, monkeypatch):
    """A token altered where it is produced, in the decode tick."""
    from repro.serving import engine as eng

    vocab = cell["config"]["vocab_size"]

    class Altered(eng.ContinuousEngine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            tick = self._tick

            def altered(*args):
                tok, logits, pos, cache = tick(*args)
                return (tok + 1) % vocab, logits, pos, cache

            self._tick = altered

    monkeypatch.setattr(eng, "ContinuousEngine", Altered)
    r = run(cell, cpu_chip)
    assert r["failed"] == 0  # every answer came, of the right length
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_lost_answers_are_counted(cell, cpu_chip, monkeypatch):
    """A request whose answer never comes fails the run."""
    from repro.serving import engine as eng

    serve = eng.ContinuousEngine.serve

    def drop_first(self, requests, on_result=None):
        def cb(i, res):
            if i and on_result is not None:
                on_result(i, res)
        return serve(self, requests, on_result=cb)

    monkeypatch.setattr(eng.ContinuousEngine, "serve", drop_first)
    r = run(cell, cpu_chip)
    assert r["failed"] > 0 and not r["correct"]


def test_every_cell_is_found_by_name():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        for trace in (False, True):
            cell = bench.load_cell(w["name"], trace)
            assert cell["metrics"]
            for m in cell["metrics"]:
                assert callable(bench.metric_reader(m["name"]))
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()


@pytest.mark.parametrize("name", ["stablelm-3b", "starcoder2-3b"])
def test_config_files_run_the_repo_config(name):
    """The file's sizes are the program's configuration but for the keys it
    lists as reduced."""
    from repro.models import get_config

    config, family = bench.load_config(name)
    assert config["family"] == "dense"
    ran = family.program_config(config)
    repo = get_config(config["arch"])
    assert config["reduced"] == ["num_hidden_layers"]
    assert ran.replace(n_layers=repo.n_layers,
                       approx=repo.approx) == repo
    assert config["published"]["num_hidden_layers"] == repo.n_layers


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "stablelm-3b.prefill_long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "not a TPU" in p.stderr


def test_recorded_logits_are_the_served_ones(cell, cpu_chip):
    """The recorder finds each sampled request's slot and ticks: the token
    the program's recorded logits put first is the token it served."""
    served = bench.build(cell, SEED)
    win = bench.window(served, cell, SEED, 0.0, None)
    picked = bench.picked_with_logits(win, cell, SEED)
    assert len(picked) == cell["traffic"]["check_sample"]
    for r in picked:
        assert r["logits"].shape == (len(r["out"]),
                                     cell["config"]["vocab_size"])
        np.testing.assert_array_equal(r["logits"].argmax(-1), r["out"])


def test_control_is_not_correct(cell):
    """calibrate.py at a tiny size: through the comparison that decides a
    run's ``correct``, the program passes and the float8 control, in its
    place at the same served positions, fails; so does a token altered
    where it is produced."""
    import calibrate

    served = bench.build(cell, SEED)
    r = calibrate.readings(served, cell, SEED)
    assert r["failed"] == 0 and r["tokens"] > 0
    assert r["program"]["correct"]
    assert not r["fp8"]["correct"]
    assert r["fp8"]["logit_err"] > 3 * r["program"]["logit_err"]
    assert not r["altered_token"]["correct"]
