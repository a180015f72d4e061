"""Tests of the benchmark itself, run by hand on the CPU:

  JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

They put ``chipbench/`` and ``src/`` on the path the way ``run.py`` does."""

import copy
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent / "src")]


def _json(*parts):
    with open(HERE.parent.joinpath(*parts)) as f:
        return json.load(f)


def tiny_cell(config_name: str) -> dict:
    """A cell of the committed configuration and mix, cut to CPU size: the
    same keys, family and code paths, small widths and lengths."""
    import bench

    config = copy.deepcopy(_json("configs", f"{config_name}.json"))
    gqa = config["num_key_value_heads"] < config["num_attention_heads"]
    config.update(hidden_size=64, intermediate_size=128 if gqa else 96,
                  num_attention_heads=6 if gqa else 4,
                  num_key_value_heads=2 if gqa else 4,
                  num_hidden_layers=2, vocab_size=300)
    mix = copy.deepcopy(_json("traffic", "prefill_long.json"))
    mix.update(batch=4, prefill_len=24, cache_len=40, queue=8, check_sample=6)
    mix["prompt"].update(median=12, min=6, max=24)
    mix["output"].update(median=5, min=2, max=12)
    return {"workload": {"name": f"{config_name}.tiny", "chips": 1},
            "config": config, "family": bench.family(config["family"]),
            "traffic": mix, "limits": {"logit_gap": 0.3, "logit_err": 0.03},
            "metrics": []}


@pytest.fixture(params=["stablelm-3b", "starcoder2-3b"])
def cell(request):
    return tiny_cell(request.param)


@pytest.fixture
def cpu_chip():
    """What ``bench.find_chip`` returns, for the CPU: the harness's look for
    a chip is skipped, the rest of a run is driven."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1,
            "peak": _json("peaks.json")["TPU v5 lite"], "device": d}
