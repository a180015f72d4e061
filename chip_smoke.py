"""Smoke test of the serving path on TPU, at stablelm-3b's published widths.

  python3 chip_smoke.py                # one chip: device, kernel, serve, parity
  python3 chip_smoke.py --four-chips   # one 2x2 host: sharded decode vs one chip

One process, no subprocesses.  Every phase prints its own ``[phase]`` lines;
any failure exits non-zero.  On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script fails at the device check and prints no result.

Phases (one chip):

* device  — ``jax.devices()[0]`` must be a TPU.
* kernel  — every member of the ``table_pack`` pack through the compiled
  ``table_pack_lookup_pallas`` / ``table_pack_grad_pallas`` on a dense grid
  over its domain, held to the pack's error budget Ea against the f64
  function and to bit-identity with ``eval_pack_ref`` / ``eval_pack_slope``.
* serve   — stablelm-3b (d_model 2560, 32 MHA heads, d_ff 6912, vocab 50304)
  in ``table_pack`` mode, random weights from ``--seed``, served through
  ``ContinuousEngine.serve`` as ``repro.launch.serve`` does.  Depth is cut to
  ``SERVE_LAYERS``; ``memory_analysis()`` of the prefill and decode
  executables must show each fits the chip with headroom.  The decode tick
  must contain the Pallas kernel
  (``tpu_custom_call``) and its logits must be finite.
* parity  — the same prompts and weights through ``table_pack_ref`` (the jnp
  oracle): first-step logit gap and greedy-token agreement are printed;
  only non-finite values fail (no logits bound is derived for the MLP
  activations yet, and random-init logits are near-tied).

``--four-chips`` runs only the mesh phase: the sharded prefill + decode step
on a (data=1, model=4) mesh against the same weights on one chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
# libtpu otherwise writes its logs to a fixed /tmp/tpu_logs, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.approx.table_pack import eval_pack_ref, eval_pack_slope, member_domain
from repro.core.functions import get as get_function
from repro.kernels.table_pack_lookup import (table_pack_grad_pallas,
                                             table_pack_lookup_pallas)
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model, get_config
from repro.serving.engine import ContinuousEngine, Request

ARCH = "stablelm-3b"
BATCH, CACHE_LEN, MAX_NEW, N_REQUESTS = 4, 256, 16, 8
HEADROOM = 0.8  # share of the chip's bytes_limit one executable may take
# The deepest cut whose prefill and decode executables fit HEADROOM x the
# v5e bytes_limit (16.909 GB) by memory_analysis(): 13.470 GB each at 25
# layers, about 0.52 GB more per layer.  The full 32 do not fit: the bf16
# weight copy is hoisted out of the layer scan (ROADMAP).
SERVE_LAYERS = 25
GRID = 1 << 16  # kernel-phase points per member
FOUR_CHIP_LAYERS = 8
GB = 1e9


class SmokeFailure(Exception):
    pass


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, phase: str, msg: str) -> None:
    if not ok:
        raise SmokeFailure(f"[{phase}] FAIL: {msg}")


def stablelm(mode: str, n_layers: int):
    """stablelm-3b at published widths, approx mode ``mode``, cut in depth."""
    cfg = get_config(ARCH)
    return cfg.replace(n_layers=n_layers,
                       approx=dataclasses.replace(cfg.approx, mode=mode))


def device_phase(want_count: int):
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", "device",
          f"jax.devices()[0] is {d.platform!r}, not a TPU")
    check(len(devs) >= want_count, "device",
          f"{len(devs)} device(s), this mode needs {want_count}")
    log("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    return devs


# ------------------------------- kernel --------------------------------------


def kernel_phase(approx) -> None:
    """Each member through the compiled kernels: within Ea of the f64
    function, and bit-identical to the jnp oracle (value and slope)."""
    pack = approx.pack()
    for name in pack.names:
        lo, hi = (float(v) for v in member_domain(pack, name))
        x32 = np.linspace(lo, hi, GRID, endpoint=False).astype(np.float32)
        x = jnp.asarray(x32)
        want = get_function(name).f(x32.astype(np.float64))
        y = np.asarray(table_pack_lookup_pallas(pack, name, x, interpret=False))
        yg, dy = (np.asarray(v) for v in
                  table_pack_grad_pallas(pack, name, x, interpret=False))
        ref = np.asarray(jax.jit(lambda v: eval_pack_ref(pack, name, v))(x))
        ref_dy = np.asarray(jax.jit(lambda v: eval_pack_slope(pack, name, v))(x))
        err = max(np.abs(y.astype(np.float64) - want).max(),
                  np.abs(yg.astype(np.float64) - want).max())
        gap = max(np.abs(y - ref).max(), np.abs(yg - ref).max())
        gap_dy = np.abs(dy - ref_dy).max()
        log("kernel", f"{name}: [{lo:g}, {hi:g}) n={GRID} worst|kernel-f|="
                      f"{err:.6e} (Ea={approx.e_a:g}); "
                      f"max|kernel-eval_pack_ref|={gap:.3e} "
                      f"max|dy-eval_pack_slope|={gap_dy:.3e}")
        check(bool(np.isfinite(y).all() and np.isfinite(yg).all()
                   and np.isfinite(dy).all()), "kernel",
              f"{name}: non-finite kernel output")
        check(err <= approx.e_a, "kernel",
              f"{name}: error {err:.6e} over Ea={approx.e_a:g}")
        check(gap == 0 and gap_dy == 0, "kernel",
              f"{name}: kernel differs from the jnp oracle (value {gap:.3e}, "
              f"slope {gap_dy:.3e})")


# ------------------------------- serve ---------------------------------------


def make_requests(vocab: int, seed: int):
    """The launcher's synthetic queue: prompts of 4..31 tokens."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, vocab, (int(n),)).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for n in rng.integers(4, 32, N_REQUESTS)]


def prompt_rows(reqs, width: int) -> np.ndarray:
    """The first BATCH prompts left-padded to ``width``, as admission does."""
    rows = np.zeros((BATCH, width), np.int32)
    for j, r in enumerate(reqs[:BATCH]):
        rows[j, width - len(r.prompt):] = r.prompt
    return rows


def executable_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def engine_sizes(n_layers: int, prefill_len: int, device) -> dict:
    """Bytes of the ContinuousEngine's prefill and decode executables at
    ``n_layers``, compiled for ``device`` from shapes alone."""
    model = build_model(stablelm("table_pack", n_layers))
    sh = SingleDeviceSharding(device)
    spec = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sh)
    params = jax.tree.map(spec, jax.eval_shape(model.init, jax.random.key(0)))
    cache = jax.tree.map(spec, model.abstract_cache(BATCH, CACHE_LEN))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sh)
    eng = ContinuousEngine(model, params, BATCH, CACHE_LEN)
    exe = eng._executables
    return {
        "prefill": executable_bytes(exe["prefill"].lower(
            params, {"tokens": i32(BATCH, prefill_len)}, cache).compile()),
        "decode_step": executable_bytes(exe["decode_step"].lower(
            params, i32(BATCH, 1), i32(BATCH), cache).compile()),
    }


def device_limit(device) -> int:
    """Bytes the runtime lets one program hold on ``device``."""
    return device.memory_stats()["bytes_limit"]


def serve_phase(devs, seed: int):
    d = devs[0]
    cfg = get_config(ARCH)
    vocab = cfg.vocab
    reqs = make_requests(vocab, seed)
    width = max(len(r.prompt) for r in reqs)
    limit = device_limit(d)
    depth = SERVE_LAYERS
    sizes = engine_sizes(depth, width, d)
    log("serve", f"depth {depth} of {cfg.n_layers} layers (cut "
                 f"{cfg.n_layers - depth}) at d_model {cfg.d_model} / "
                 f"{cfg.n_heads} heads / d_ff {cfg.d_ff} / vocab {vocab}; "
                 f"prefill {sizes['prefill'] / GB:.3f} GB, decode tick "
                 f"{sizes['decode_step'] / GB:.3f} GB vs {HEADROOM:g} x "
                 f"bytes_limit {limit / GB:.3f} GB")
    check(max(sizes.values()) <= HEADROOM * limit, "serve",
          f"{depth} layers do not fit {HEADROOM:g} x bytes_limit")

    model = build_model(stablelm("table_pack", depth))
    params = model.init(jax.random.key(seed))
    engine = ContinuousEngine(model, params, BATCH, CACHE_LEN, temperature=0.0)
    t0 = time.time()
    results = engine.serve(reqs)
    wall = time.time() - t0
    tokens = sum(r.steps for r in results)
    log("serve", f"served {len(results)} requests, {tokens} tokens through "
                 f"ContinuousEngine (batch {BATCH}, cache {CACHE_LEN}, "
                 f"max-new {MAX_NEW}); host wall {wall:.1f} s incl. compile; "
                 f"executables {engine.compile_counts()}")
    check(tokens == N_REQUESTS * MAX_NEW, "serve",
          f"{tokens} tokens, expected {N_REQUESTS * MAX_NEW}")
    check(all(((r.tokens >= 0) & (r.tokens < vocab)).all() for r in results),
          "serve", "token id out of the vocabulary")

    rows = jnp.asarray(prompt_rows(reqs, width))
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": rows}, model.init_cache(BATCH, CACHE_LEN))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((BATCH,), width, jnp.int32)
    tick = engine._executables["decode_step"].lower(
        params, tok, pos, cache).compile()
    kernels = tick.as_text().count("tpu_custom_call")
    _, tick_logits, _, _ = tick(params, tok, pos, cache)
    finite = bool(jnp.isfinite(logits[:, :vocab]).all()
                  and jnp.isfinite(tick_logits[:, :vocab]).all())
    log("serve", f"decode tick HLO holds {kernels} tpu_custom_call op(s); "
                 f"prefill and tick logits finite: {finite}; peak device "
                 f"bytes {(d.memory_stats() or {}).get('peak_bytes_in_use', 0) / GB:.3f}"
                 f" GB")
    check(kernels > 0, "serve", "decode tick has no Pallas kernel")
    check(finite, "serve", "non-finite logits")
    return depth, params, reqs, results, rows, logits


def parity_phase(depth, params, reqs, results, rows, logits, vocab: int):
    ref_model = build_model(stablelm("table_pack_ref", depth))
    ref_logits, _ = jax.jit(ref_model.prefill)(
        params, {"tokens": rows}, ref_model.init_cache(BATCH, CACHE_LEN))
    a, b = logits[:, :vocab], ref_logits[:, :vocab]
    check(bool(jnp.isfinite(b).all()), "parity", "non-finite oracle logits")
    gap = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    first = float(jnp.mean(jnp.argmax(a, -1) == jnp.argmax(b, -1)))
    ref_results = ContinuousEngine(ref_model, params, BATCH, CACHE_LEN,
                                   temperature=0.0).serve(reqs)
    same = [np.mean(r.tokens == q.tokens) for r, q in zip(results, ref_results)]
    log("parity", f"table_pack vs table_pack_ref: first-step max|dlogit| "
                  f"{gap:.6e} (logit scale {float(jnp.max(jnp.abs(b))):.4g}); "
                  f"first-token agreement {first:.3f}; greedy-token agreement "
                  f"{float(np.mean(same)):.3f} over {len(same)} requests")


# ------------------------------ four chips -----------------------------------


def device_bytes(tree) -> dict:
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            out[s.device.id] = out.get(s.device.id, 0) + s.data.nbytes
    return out


def mesh_phase(devs, seed: int) -> None:
    from repro.launch.mesh import make_debug_mesh
    from repro.parallel.cache_specs import cache_pspecs
    from repro.parallel.params import param_pspecs, shardings_from_specs
    from repro.parallel.sharding import use_sharding

    model = build_model(stablelm("table_pack", FOUR_CHIP_LAYERS))
    vocab = model.cfg.vocab
    reqs = make_requests(vocab, seed)
    width = max(len(r.prompt) for r in reqs)
    rows = jnp.asarray(prompt_rows(reqs, width))
    pos = jnp.full((BATCH,), width, jnp.int32)

    params = model.init(jax.random.key(seed))
    cache = model.init_cache(BATCH, CACHE_LEN)
    logits1, cache1 = jax.jit(model.prefill)(params, {"tokens": rows}, cache)
    tok = jnp.argmax(logits1, -1)[:, None].astype(jnp.int32)
    step1, _ = jax.jit(model.decode_step)(params, tok, pos, cache1)
    log("mesh", f"one chip ({devs[0].device_kind}): stablelm-3b "
                f"{FOUR_CHIP_LAYERS} layers, prefill + one decode step")

    mesh = make_debug_mesh(1, 4)
    psh = shardings_from_specs(mesh, param_pspecs(model.abstract_params(), mesh))
    csh = shardings_from_specs(mesh, cache_pspecs(
        model.abstract_cache(BATCH, CACHE_LEN), mesh))
    params_s = jax.device_put(params, psh)
    cache_s = jax.device_put(cache, csh)
    placed = device_bytes((params_s, cache_s))
    log("mesh", "bytes per device after placement: " + ", ".join(
        f"dev{k} {v / GB:.3f} GB" for k, v in sorted(placed.items())))
    with use_sharding(mesh):
        logits4, cache4 = jax.jit(model.prefill)(params_s, {"tokens": rows},
                                                 cache_s)
        step4, _ = jax.jit(model.decode_step)(params_s, tok, pos, cache4)
    for label, one, four in (("prefill", logits1, logits4),
                             ("decode step", step1, step4)):
        a = np.asarray(one, np.float32)[:, :vocab]
        b = np.asarray(jax.device_get(four), np.float32)[:, :vocab]
        check(bool(np.isfinite(b).all()), "mesh", f"non-finite {label} logits")
        log("mesh", f"{label}: (data=1, model=4) vs one chip max|dlogit| "
                    f"{np.abs(a - b).max():.6e} (logit scale "
                    f"{np.abs(a).max():.4g}); argmax agreement "
                    f"{np.mean(a.argmax(-1) == b.argmax(-1)):.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-decode phase on a (1, 4) mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the request queue")
    args = ap.parse_args()
    use_compile_cache()
    try:
        devs = device_phase(4 if args.four_chips else 1)
        if args.four_chips:
            mesh_phase(devs, args.seed)
        else:
            approx = stablelm("table_pack", 1).approx
            kernel_phase(approx)
            served = serve_phase(devs, args.seed)
            parity_phase(*served, vocab=get_config(ARCH).vocab)
    except SmokeFailure as e:
        print(e, file=sys.stderr, flush=True)
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
