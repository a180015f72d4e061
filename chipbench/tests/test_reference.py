"""The dense family's float32 reference follows the program's equations: at
a tiny size on the CPU, the program in exact mode computing in float32 gives
the same logits, for the MHA layout (stablelm-3b) and the padded GQA one
(starcoder2-3b)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import bench
import precision
import weights


def program_f32(cell):
    from repro.models import build_model

    cfg = cell["family"].program_config(cell["config"])
    cfg = cfg.replace(compute_dtype="float32",
                      approx=dataclasses.replace(cfg.approx, mode="exact"))
    return build_model(cfg)


def test_reference_matches_program_in_float32(cell):
    model = program_f32(cell)
    params = weights.make(model.abstract_params(), 3, cell["family"].scale)
    vocab = cell["config"]["vocab_size"]
    tokens = np.random.default_rng(0).integers(0, vocab, 20).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(model.train_logits)(
            params, {"tokens": jnp.asarray(tokens)[None]})
    got = cell["family"].logits(params, tokens, cell["config"], 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0, :, :vocab],
                               atol=2e-4, rtol=2e-4)


def test_int8_control_departs_from_float32(cell):
    model = program_f32(cell)
    params = weights.make(model.abstract_params(), 3, cell["family"].scale)
    tokens = np.arange(1, 21, dtype=np.int32)
    ref = cell["family"].logits
    exact = np.asarray(ref(params, tokens, cell["config"], 0))
    low = np.asarray(ref(params, tokens, cell["config"], 0,
                         precision.int8_dot))
    gap = np.abs(low - exact).max()
    assert 1e-3 < gap < 0.5 * np.abs(exact).max()


def test_head_layout():
    dense = bench.family("dense")
    heads, kv = dense.head_layout(32, 24, 2)
    assert list(heads) == list(range(12)) + list(range(16, 28))
    assert list(kv) == [0] * 12 + [1] * 12
    heads, kv = dense.head_layout(32, 32, 32)
    assert list(heads) == list(kv) == list(range(32))
