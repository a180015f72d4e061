"""The architecture comes from the configuration's family: the dense family
gives the weights that the harness gave before families existed, and a
configuration that names a family of its own reaches it through every part of
the harness with nothing but new files."""

import copy
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import devtrace
import weights
from conftest import tiny_cell
from traffic import seed_words

HERE = Path(__file__).resolve().parent
HARNESS, REPO = bench.HERE, bench.REPO  # the committed ones
SEED = 2**33 + 77


# The weights as the harness made them before families existed, frozen here:
# the dense family's ``scale`` and ``weights.make`` have to give them again.
def frozen_scale(path: tuple, shape: tuple, stacked: bool):
    names = [getattr(p, "key", str(p)) for p in path]
    if names[-1] == "g":
        return None  # norm gain
    if "table" in names or "wo" in names:
        return 0.02
    fan_in = shape[1] if stacked else shape[0]
    return fan_in ** -0.5


def frozen_make(shapes, seed: int):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.key(lo), hi)

    def build(k):
        out = []
        for i, (path, s) in enumerate(leaves):
            stacked = getattr(path[0], "key", None) == "layers"
            scale = frozen_scale(path, s.shape, stacked)
            if scale is None:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                out.append(scale * jax.random.normal(
                    jax.random.fold_in(k, i), s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key)


def shapes_of(config: dict, family):
    from repro.models import build_model

    return build_model(family.program_config(config)).abstract_params()


@pytest.mark.parametrize("name", ["stablelm-3b", "starcoder2-3b"])
def test_dense_scale_is_the_frozen_rule(name):
    """Every leaf of the configuration's tree, at its committed sizes."""
    config, family = bench.load_config(name)
    leaves = jax.tree_util.tree_flatten_with_path(shapes_of(config, family))[0]
    assert len(leaves) > 10
    for path, s in leaves:
        names = tuple(getattr(p, "key", str(p)) for p in path)
        stacked = names[0] == "layers"
        assert family.scale(names, s.shape) == \
            frozen_scale(path, s.shape, stacked), names


@pytest.mark.parametrize("name", ["stablelm-3b", "starcoder2-3b"])
def test_make_gives_the_frozen_weights(name):
    cell = tiny_cell(name)
    shapes = shapes_of(cell["config"], cell["family"])
    got = weights.make(shapes, SEED, cell["family"].scale)
    want = frozen_make(shapes, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------- a family of its own -------------------------------


WORKLOAD = "recording-tiny.tiny"


def lay_out(root: Path, config: dict) -> None:
    """A checkout at ``root`` that holds the committed harness and, as new
    files and entries alone, a tiny configuration ``config`` with a cell of
    its own, and the recording family."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copytree(HARNESS, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    here = root / "chipbench"
    shutil.copy(HERE / "data" / "recording.py", here / "families")
    cell = tiny_cell("stablelm-3b")
    (here / "configs" / "recording-tiny.json").write_text(json.dumps(config))
    (here / "traffic" / "tiny.json").write_text(json.dumps(cell["traffic"]))
    (here / "limits" / f"{WORKLOAD}.json").write_text(
        json.dumps(cell["limits"]))
    spec["configs"].append({"name": "recording-tiny", "source": "test",
                            "file": "chipbench/configs/recording-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": WORKLOAD, "config": "recording-tiny",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(WORKLOAD)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A function that lays out a checkout with a configuration and points
    the harness at it."""
    def with_config(config):
        lay_out(tmp_path, config)
        monkeypatch.setattr(bench, "REPO", tmp_path)
        monkeypatch.setattr(bench, "HERE", tmp_path / "chipbench")

    return with_config


def recording_config() -> dict:
    config = copy.deepcopy(tiny_cell("stablelm-3b")["config"])
    config["family"] = "recording"
    return config


def test_a_new_family_needs_only_new_files(checkout, cpu_chip):
    """A traced run, the window's counters and the kernel's roofline of a
    cell whose configuration names the recording family."""
    checkout(recording_config())
    cell = bench.load_cell(WORKLOAD, trace=True)
    family = cell["family"]
    assert Path(family.__file__) == \
        bench.HERE / "families" / "recording.py"
    assert {m["name"] for m in cell["metrics"]} >= {"step_mfu",
                                                    "act_kernel_roofline"}

    r = bench.run_cell(cell, SEED, 0.5, True, cpu_chip, time.perf_counter())
    assert r["correct"] and r["failed"] == 0
    assert r["metrics"]["step_mfu"]["value"] > 0
    called = {name for name, _ in family.CALLS}
    assert called >= {"program_config", "scale", "logits", "request_flops"}
    # weights.make: one scale per leaf, for the program's tree and again
    # for the reference's
    n_leaves = len(jax.tree.leaves(shapes_of(cell["config"], family)))
    assert sum(name == "scale" for name, _ in family.CALLS) == 2 * n_leaves

    # The window records the family's counters; on a trace recorded on a
    # v5e the kernel's roofline counts the family's elements.
    family.CALLS.clear()
    served = bench.build(cell, SEED)
    win = bench.window(served, cell, SEED, 0.0, None)
    assert win["counters"]["cache_len"] == cell["traffic"]["cache_len"]
    recorded = devtrace.read_saved(str(HERE / "data" /
                                       "v5e_stablelm_queue_start.json.gz"))
    run = {"trace": recorded, "config": cell["config"], "family": family,
           "counters": win["counters"], "peak": cpu_chip["peak"],
           "requests": win["requests"], "window_s": win["window_s"],
           "chips": 1}
    roofline = bench.metric_reader("act_kernel_roofline")(run)
    assert ("act_elements", (cell["config"], win["counters"])) in \
        family.CALLS
    dense = dict(run, family=bench.family("dense"))
    assert roofline == bench.metric_reader("act_kernel_roofline")(dense) > 0
    assert bench.metric_reader("step_mfu")(run) == \
        bench.metric_reader("step_mfu")(dense) > 0


@pytest.mark.parametrize("family,why", [(None, "names no family"),
                                        ("sparse_moe", "has no module")])
def test_a_config_without_a_family_module_is_refused(checkout, family, why):
    config = recording_config()
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    checkout(config)
    with pytest.raises(SystemExit, match=why) as e:
        bench.load_cell(WORKLOAD, trace=False)
    assert "chipbench/configs/recording-tiny.json" in str(e.value)
