"""Multi-device integration tests.

Device count is locked at first jax init, so these run in SUBPROCESSES with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the smoke tests keep the
default 1 device, per the assignment).  Covers: sharded train step on a (2,4)
mesh, WUS layouts, elastic checkpoint restore onto a different mesh, and the
spec builders' divisibility guarantees.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.models import get_config
from repro.models.registry import ARCH_IDS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + ":" + REPO
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
from tests.test_archs import reduced, make_batch
from repro.models import build_model
from repro.parallel.params import param_pspecs, zero1_pspecs, shardings_from_specs
from repro.parallel.sharding import use_sharding, default_rules
from repro.train.loop import make_train_step, state_pspecs, work_pspecs
from repro.optim import adamw
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = reduced("stablelm-3b").replace(d_model=64, d_ff=128, n_heads=4, n_kv_heads=4)
model = build_model(cfg)
"""


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    out = run_subprocess(PREAMBLE + """
batch = make_batch(cfg, B=8, S=16)
params = model.init(jax.random.key(0))
state = {"params": params, "opt": adamw.init(params),
         "step": jnp.zeros((), jnp.int32)}
opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10, clip_norm=0.0)

# single-device reference
ref_state, ref_metrics = make_train_step(model, opt)(state, batch)

# sharded WUS step
specs = state_pspecs(model, mesh)
sh = shardings_from_specs(mesh, specs)
wsh = shardings_from_specs(mesh, work_pspecs(model, mesh))
msh = sh["params"]
state_sharded = jax.device_put(state, sh)
with use_sharding(mesh):
    step = jax.jit(make_train_step(model, opt, work_shardings=wsh,
                                   master_shardings=msh),
                   in_shardings=(sh, None), out_shardings=(sh, None))
    new_state, metrics = step(state_sharded, batch)
print("LOSS", float(ref_metrics["loss"]), float(metrics["loss"]))
# WUS runs bf16 forward; compare at bf16-appropriate tolerance
assert abs(float(ref_metrics["loss"]) - float(metrics["loss"])) < 0.05
a = np.asarray(jax.device_get(jax.tree.leaves(new_state["params"])[0]))
b = np.asarray(jax.device_get(jax.tree.leaves(ref_state["params"])[0]))
np.testing.assert_allclose(a, b, atol=5e-3)
print("SHARDED_STEP_OK")
""")
    assert "SHARDED_STEP_OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    out = run_subprocess(PREAMBLE + f"""
from repro.train import CheckpointManager
params = model.init(jax.random.key(1))
state = {{"params": params, "opt": adamw.init(params),
         "step": jnp.asarray(3, jnp.int32)}}
specs = state_pspecs(model, mesh)
sh = shardings_from_specs(mesh, specs)
state_sharded = jax.device_put(state, sh)
mgr = CheckpointManager({str(tmp_path)!r}, keep=2)
mgr.save(3, state_sharded)

# restore onto a DIFFERENT mesh shape (4, 2)
mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
specs2 = state_pspecs(model, mesh2)
sh2 = shardings_from_specs(mesh2, specs2)
abstract = jax.eval_shape(lambda: state)
step, restored = mgr.restore_latest(abstract, sh2)
assert step == 3
for (pa, a), (pb, b) in zip(
    jax.tree_util.tree_flatten_with_path(state)[0],
    jax.tree_util.tree_flatten_with_path(restored)[0]):
    np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                  np.asarray(jax.device_get(b)))
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_sharded_loss_equals_unsharded_loss():
    """Pure sharding change must not change the math (exact same fwd graph)."""
    out = run_subprocess(PREAMBLE + """
batch = make_batch(cfg, B=8, S=16)
params = model.init(jax.random.key(2))
l_ref = float(model.loss(params, batch))
pspecs = param_pspecs(model.abstract_params(), mesh)
psh = shardings_from_specs(mesh, pspecs)
params_sharded = jax.device_put(params, psh)
with use_sharding(mesh):
    l_sh = float(jax.jit(model.loss)(params_sharded, batch))
print("LOSSES", l_ref, l_sh)
assert abs(l_ref - l_sh) < 1e-3  # sharded reductions reorder float sums
print("LOSS_MATCH_OK")
""")
    assert "LOSS_MATCH_OK" in out


@pytest.mark.slow
def test_decode_sharded_matches_unsharded():
    out = run_subprocess(PREAMBLE + """
from repro.parallel.cache_specs import cache_pspecs
params = model.init(jax.random.key(3))
batch = make_batch(cfg, B=8, S=8)
cache = model.init_cache(8, 32)
logits_ref, cache_ref = model.prefill(params, batch, cache)
tok = jnp.argmax(logits_ref, -1)[:, None].astype(jnp.int32)
step_ref, _ = model.decode_step(params, tok, jnp.asarray(8, jnp.int32), cache_ref)

pspecs = param_pspecs(model.abstract_params(), mesh)
psh = shardings_from_specs(mesh, pspecs)
csh = shardings_from_specs(mesh, cache_pspecs(
    jax.eval_shape(lambda: cache), mesh))
params_s = jax.device_put(params, psh)
cache_s = jax.device_put(cache, csh)
with use_sharding(mesh):
    logits_s, cache_s = jax.jit(model.prefill)(params_s, batch, cache_s)
    step_s, _ = jax.jit(model.decode_step)(params_s, tok,
                                           jnp.asarray(8, jnp.int32), cache_s)
np.testing.assert_allclose(np.asarray(step_ref), np.asarray(jax.device_get(step_s)),
                           atol=2e-2, rtol=2e-2)
print("DECODE_SHARDED_OK")
""")
    assert "DECODE_SHARDED_OK" in out


# ---------------- spec-builder unit tests (no devices needed) -------------------


def test_param_specs_divisibility_all_archs():
    """Every spec must divide its dim — for every assigned arch, on both meshes."""
    from jax.sharding import PartitionSpec
    from repro.models import build_model
    from repro.parallel.params import param_pspecs, zero1_pspecs

    mesh_axes = {"data": 16, "model": 16}

    class FakeMesh:
        axis_names = tuple(mesh_axes)
        devices = np.empty((16, 16))

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        abstract = model.abstract_params()
        for specs in (param_pspecs(abstract, FakeMesh()),
                      zero1_pspecs(abstract, FakeMesh())):
            flat_p = jax.tree_util.tree_flatten_with_path(abstract)[0]
            flat_s = jax.tree_util.tree_flatten(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
            assert len(flat_p) == len(flat_s)
            for (path, leaf), spec in zip(flat_p, flat_s):
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    size = int(np.prod([mesh_axes[a] for a in
                                        (ax if isinstance(ax, tuple) else (ax,))]))
                    assert dim % size == 0, (arch, path, leaf.shape, spec)


def test_cache_specs_divisibility_all_archs():
    from jax.sharding import PartitionSpec
    from repro.models import build_model
    from repro.parallel.cache_specs import cache_pspecs

    mesh_axes = {"data": 16, "model": 16}

    class FakeMesh:
        axis_names = tuple(mesh_axes)
        devices = np.empty((16, 16))

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        cache = model.abstract_cache(128, 1024)
        specs = cache_pspecs(cache, FakeMesh())
        flat_c = jax.tree_util.tree_flatten_with_path(cache)[0]
        flat_s = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
        for (path, leaf), spec in zip(flat_c, flat_s):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                size = int(np.prod([mesh_axes[a] for a in
                                    (ax if isinstance(ax, tuple) else (ax,))]))
                assert dim % size == 0, (arch, path, leaf.shape, spec)


@pytest.mark.slow
def test_table_pack_kernel_runs_per_shard_on_mesh():
    """The Pallas pack kernel cannot be partitioned by XLA; under a mesh it
    runs per shard (shard_map) and gives the unsharded bits, values and
    grads, and the sharded decode step in table_pack mode matches."""
    out = run_subprocess(PREAMBLE + """
import dataclasses
from repro.parallel.cache_specs import cache_pspecs
from repro.approx import ApproxConfig
act = ApproxConfig(mode="table_pack", e_a=1e-4, omega=0.2).unary("silu")
x = jnp.asarray(np.random.default_rng(0).normal(0, 4, (8, 3, 64)), jnp.float32)
gsum = lambda v: jax.grad(lambda u: act(u).sum())(v)
want, gwant = jax.jit(act)(x), jax.jit(gsum)(x)
with use_sharding(mesh):
    got, ggot = jax.jit(act)(x), jax.jit(gsum)(x)
np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
np.testing.assert_array_equal(np.asarray(ggot), np.asarray(gwant))

cfg = cfg.replace(approx=dataclasses.replace(cfg.approx, mode="table_pack"))
model = build_model(cfg)
params = model.init(jax.random.key(4))
batch = make_batch(cfg, B=8, S=8)
cache = model.init_cache(8, 32)
logits_ref, cache_ref = model.prefill(params, batch, cache)
tok = jnp.argmax(logits_ref, -1)[:, None].astype(jnp.int32)
step_ref, _ = model.decode_step(params, tok, jnp.asarray(8, jnp.int32), cache_ref)
psh = shardings_from_specs(mesh, param_pspecs(model.abstract_params(), mesh))
csh = shardings_from_specs(mesh, cache_pspecs(jax.eval_shape(lambda: cache), mesh))
with use_sharding(mesh):
    _, cache_s = jax.jit(model.prefill)(jax.device_put(params, psh), batch,
                                        jax.device_put(cache, csh))
    step_s, _ = jax.jit(model.decode_step)(jax.device_put(params, psh), tok,
                                           jnp.asarray(8, jnp.int32), cache_s)
np.testing.assert_allclose(np.asarray(step_ref), np.asarray(jax.device_get(step_s)),
                           atol=2e-2, rtol=2e-2)
print("PACK_MESH_OK")
""")
    assert "PACK_MESH_OK" in out
