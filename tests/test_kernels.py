"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle, sweeping
shapes / dtypes / table geometries, plus numeric-contract tests vs the exact fns."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.approx import ApproxConfig, from_spec
from repro.approx.jax_table import eval_table_slope
from repro.core import build_table, get_function
from repro.kernels.ops import table_lookup
from repro.kernels.ref import table_lookup_ref

RNG = np.random.default_rng(42)


def _table(name="silu", ea=1e-4, alg="hierarchical", omega=0.2):
    return from_spec(build_table(name, ea, algorithm=alg, omega=omega))


SHAPES = [
    (8,),  # sub-lane
    (128,),  # one lane row
    (513,),  # pad + slice
    (4, 96),
    (2, 3, 257),  # odd everything
    (1, 8192),  # multiple row blocks
    (16, 1024),
    (2, 2, 2, 130),
]


class TestPallasVsOracle:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_shapes_f32(self, shape):
        jt = _table()
        x = jnp.asarray(RNG.normal(0, 5, size=shape).astype(np.float32))
        got = table_lookup(jt, x)
        want = table_lookup_ref(jt, x)
        assert got.shape == x.shape and got.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, rtol=0)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
    def test_dtypes(self, dtype):
        jt = _table("gelu")
        x = jnp.asarray(RNG.normal(0, 3, size=(4, 384)).astype(np.float32)).astype(dtype)
        got = table_lookup(jt, x)
        want = table_lookup_ref(jt, x)
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32),
            np.asarray(want, dtype=np.float32),
            atol=2e-2 if dtype != jnp.float32 else 1e-6,
            rtol=0,
        )

    @pytest.mark.parametrize(
        "name,alg,ea",
        [
            ("log", "binary", 1.22e-4),
            ("exp", "sequential", 1e-5),
            ("tanh", "hierarchical", 1e-4),
            ("sigmoid_sym", "hierarchical", 1e-5),
            ("gauss", "sequential", 1e-4),
            ("gelu", "hierarchical", 1e-4),
            ("softplus", "binary", 1e-3),
        ],
    )
    def test_table_geometries(self, name, alg, ea):
        """Different functions -> different #intervals / footprints / domains."""
        fn = get_function(name)
        jt = from_spec(build_table(name, ea, algorithm=alg, omega=0.15))
        lo, hi = fn.interval
        x = jnp.asarray(
            RNG.uniform(lo - 0.1 * (hi - lo), hi + 0.1 * (hi - lo), size=(3, 640)).astype(
                np.float32
            )
        )
        for ex in (False, True):
            got = table_lookup(jt, x, extrapolate=ex)
            want = table_lookup_ref(jt, x, extrapolate=ex)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-6
            )

    def test_block_geometry_sweep(self):
        from repro.kernels.table_lookup import table_lookup_pallas

        jt = _table()
        x = jnp.asarray(RNG.normal(0, 5, size=(5000,)).astype(np.float32))
        want = table_lookup_ref(jt, x)
        for block_rows, lane in [(8, 128), (32, 256), (256, 512), (1024, 128)]:
            got = table_lookup_pallas(jt, x, block_rows=block_rows, lane=lane)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    def test_inside_interval_error_bound(self):
        """Kernel output obeys the paper's Ea bound inside the interval (f32 slack)."""
        ea = 1e-4
        for name in ["gelu", "silu", "tanh", "sigmoid_sym"]:
            fn = get_function(name)
            jt = from_spec(build_table(name, ea, algorithm="sequential", omega=0.15))
            lo, hi = fn.interval
            xs = jnp.asarray(np.linspace(lo, hi - 1e-4, 20001, dtype=np.float32))
            y = table_lookup(jt, xs)
            exact = np.asarray(fn.f(np.asarray(xs, dtype=np.float64)))
            err = float(np.max(np.abs(np.asarray(y, dtype=np.float64) - exact)))
            assert err <= ea + 1e-5, (name, err)


class TestGradients:
    def test_table_slope_matches_fd(self):
        """custom_jvp slope == finite difference of the surrogate (away from knots)."""
        cfg = ApproxConfig(mode="table_ref", e_a=1e-4)
        f = cfg.unary("gelu")
        x = jnp.asarray(RNG.uniform(-6, 6, size=(256,)).astype(np.float32))
        g = jax.vmap(jax.grad(f))(x)
        eps = 1e-3
        fd = (f(x + eps) - f(x - eps)) / (2 * eps)
        # knot crossings make a few FD samples disagree; compare medians robustly
        diff = np.abs(np.asarray(g) - np.asarray(fd))
        assert np.percentile(diff, 90) < 1e-2

    def test_exact_grad_mode(self):
        cfg = ApproxConfig(mode="table_ref", e_a=1e-3, exact_grad=True)
        f = cfg.unary("tanh")
        x = jnp.linspace(-3, 3, 101)
        g = jax.vmap(jax.grad(f))(x)
        np.testing.assert_allclose(
            np.asarray(g), 1 - np.tanh(np.asarray(x)) ** 2, atol=1e-5
        )

    def test_grad_through_pallas(self):
        cfg = ApproxConfig(mode="table_pallas", e_a=1e-4)
        f = cfg.unary("silu")
        x = jnp.asarray(RNG.normal(0, 2, size=(33, 65)).astype(np.float32))
        loss = lambda v: (f(v) ** 2).sum()
        g = jax.grad(loss)(x)
        assert g.shape == x.shape and bool(jnp.all(jnp.isfinite(g)))

    def test_slope_zero_outside_when_clamped(self):
        jt = _table("tanh", ea=1e-4)
        s = eval_table_slope(jt, jnp.asarray([-100.0, 100.0]))
        np.testing.assert_allclose(np.asarray(s), [0.0, 0.0], atol=1e-7)


class TestSoftmaxBackend:
    def test_table_softmax_close_and_normalized(self):
        cfg = ApproxConfig(mode="table_ref", e_a=1e-6, softmax_table=True)
        x = jnp.asarray(RNG.normal(0, 4, size=(8, 128)).astype(np.float32))
        sm = cfg.softmax(x)
        np.testing.assert_allclose(np.asarray(sm.sum(-1)), 1.0, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(sm), np.asarray(jax.nn.softmax(x)), atol=5e-4
        )

    def test_table_softmax_masked(self):
        cfg = ApproxConfig(mode="table_ref", e_a=1e-6, softmax_table=True)
        x = jnp.asarray(RNG.normal(0, 2, size=(4, 16)).astype(np.float32))
        mask = jnp.arange(16) < 9
        sm = cfg.softmax(x, where=mask[None, :])
        assert float(sm[:, 9:].max()) == 0.0
        np.testing.assert_allclose(np.asarray(sm.sum(-1)), 1.0, atol=1e-5)


class TestFusedGradKernel:
    def test_fused_matches_separate(self):
        from repro.kernels.table_grad import table_lookup_grad_pallas
        from repro.approx.jax_table import eval_table_ref, eval_table_slope

        for name, ex in [("gelu", True), ("tanh", False), ("sigmoid_sym", False)]:
            jt = from_spec(build_table(name, 1e-4, algorithm="hierarchical",
                                       omega=0.2))
            x = jnp.asarray(RNG.normal(0, 4, size=(7, 193)).astype(np.float32))
            y, dy = table_lookup_grad_pallas(jt, x, extrapolate=ex)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(eval_table_ref(jt, x, extrapolate=ex)),
                atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(dy),
                np.asarray(eval_table_slope(jt, x, extrapolate=ex)), atol=1e-6)

    def test_pallas_grad_path_uses_fused(self):
        cfg = ApproxConfig(mode="table_pallas", e_a=1e-4)
        f = cfg.unary("silu")
        x = jnp.asarray(RNG.normal(0, 2, size=(256,)).astype(np.float32))
        y, vjp = jax.vjp(lambda v: f(v).sum(), x)
        (g,) = vjp(jnp.ones(()))
        g_ref = jax.vmap(jax.grad(ApproxConfig(mode="table_ref",
                                               e_a=1e-4).unary("silu")))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)


class TestBackendDecisions:
    """Interpret mode and the compile cache are each decided in one place."""

    @pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
    def test_interpret_follows_backend(self, backend, want, monkeypatch):
        from repro.kernels.table_lookup import resolve_interpret

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert resolve_interpret(None) is want
        # an explicit choice always wins (tests steer it)
        assert resolve_interpret(not want) is (not want)

    def test_other_backend_raises(self, monkeypatch):
        from repro.kernels.table_lookup import resolve_interpret

        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            resolve_interpret(None)
        assert resolve_interpret(True) is True

    def test_compile_cache_placement(self, monkeypatch, tmp_path):
        import os

        from repro.launch import compile_cache

        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert updates == []  # JAX reads the variable itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert updates == [("jax_compilation_cache_dir", path)]
        root = os.path.dirname(path)
        assert os.path.basename(path) == ".jax_cache"
        assert os.path.isfile(os.path.join(root, "chip_smoke.py"))
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
