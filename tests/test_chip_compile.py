"""Compiles for a described TPU v5e chip: the serving path's Pallas kernels
and decode tick must pass the TPU compiler (Mosaic), with no chip attached.

Interpret mode never shows what the chip's compiler refuses (a 1-D VMEM
gather, a misaligned slice); these compiles do, at real widths, in a second or
two each.  The topology is described inside a fixture so that only the
worker running this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.approx import ApproxConfig
from repro.kernels.table_pack_lookup import (table_pack_grad_pallas,
                                             table_pack_lookup_pallas)
from repro.models import build_model, get_config
from repro.serving.engine import ContinuousEngine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # libtpu otherwise writes its logs to a fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """Entries compiled for a described chip cannot be read back without
    one: keep the persistent cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _pack():
    cfg = get_config("stablelm-3b")
    return ApproxConfig(mode="table_pack", e_a=cfg.approx.e_a,
                        algorithm=cfg.approx.algorithm,
                        omega=cfg.approx.omega).pack()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [(4, 6912), (4 * 64, 2560)],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("entry", [table_pack_lookup_pallas,
                                   table_pack_grad_pallas],
                         ids=["lookup", "grad"])
def test_pack_kernel_compiles(entry, shape, dtype, one_chip,
                              no_persistent_cache):
    pack = _pack()
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(lambda v: entry(pack, "silu", v, extrapolate=True,
                                       interpret=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_tick_compiles(one_chip, no_persistent_cache, monkeypatch):
    """stablelm-3b at published widths, two layers, table_pack mode: the
    ContinuousEngine tick compiles with the pack kernel inside."""
    # the model's kernels read the backend to pick compiled mode; the
    # described chip is not the default backend here, so report it as such
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("stablelm-3b")
    cfg = cfg.replace(n_layers=2, approx=ApproxConfig(
        mode="table_pack", e_a=cfg.approx.e_a,
        algorithm=cfg.approx.algorithm, omega=cfg.approx.omega))
    model = build_model(cfg)
    spec = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
    params = jax.tree.map(spec, model.abstract_params())
    cache = jax.tree.map(spec, model.abstract_cache(4, 256))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    tick = ContinuousEngine(model, params, 4, 256)._executables["decode_step"]
    compiled = tick.lower(params, tok, pos, cache).compile()
    assert "tpu_custom_call" in compiled.as_text()
