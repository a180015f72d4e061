"""Batched serving example: prefill + decode over a request queue with the KV
cache on device, table-backend activations, and a throughput report.

Run:  PYTHONPATH=src python examples/serve_decode.py --requests 6 --max-new 12

``--scheduler continuous`` (the default) serves the queue through the
ContinuousEngine: freed slots are refilled mid-stream from the admission
queue, so decode batches stay full; ``--scheduler static`` is the PR 1
fixed-group baseline.  Throughput counts only the tokens each request
actually kept (per-request EOS/budget trimming), and the wasted-slot-step
fraction shows what the scheduler left on the table.

``--routed-demo`` instead demonstrates RoutedPack: a different activation per
expert slot evaluated in ONE call (dynamic fn_id dispatch — the routing is a
runtime operand, so re-routing the slots reuses the same compiled executable).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.approx import TABLE_MODES, ApproxConfig
from repro.models import build_model, get_config
from repro.models.common import routed_activation
from repro.serving.engine import (ContinuousEngine, DecodeEngine, Request,
                                  serve_static)

MODES = ["exact", *TABLE_MODES]


def routed_demo(mode: str, n_slots: int = 6, d: int = 256) -> None:
    """Different activation per expert slot, one dispatch, one executable."""
    cfg = ApproxConfig(mode=mode, e_a=1e-4, omega=0.2)
    slots = tuple(("gelu", "silu", "tanh", "sigmoid", "softplus", "exp")[i % 6]
                  for i in range(n_slots))
    f = jax.jit(routed_activation(cfg, slots))
    x = jnp.asarray(np.random.default_rng(0).normal(0, 2, (n_slots, d))
                    .astype(np.float32))
    y = np.asarray(f(x))
    # parity: each slot must match its own static single-function dispatch
    worst = 0.0
    for i, name in enumerate(slots):
        ys = np.asarray(jax.jit(cfg.unary(name))(x[i]))
        worst = max(worst, float(np.max(np.abs(y[i] - ys))))
    print(f"mode={mode}: routed {n_slots} slots x {d} features "
          f"({','.join(slots)}) in one call; max |routed - static| = {worst:g}")
    assert worst == 0.0, "routed dispatch must match static dispatch bitwise"
    print("routed_demo OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--mode", default="table_ref", choices=MODES)
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "static"],
                    help="continuous = admission queue + mid-stream slot "
                         "refill (full decode batches); static = PR 1 "
                         "fixed-group baseline")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--attn-table", action="store_true",
                    help="TableFlash: serve flash attention's softmax exponent"
                         " from the pack's exp_neg member (table modes only)")
    ap.add_argument("--routed-demo", action="store_true",
                    help="run the per-slot routed-activation demo and exit")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a ScopeKit Chrome-trace JSON of the serve "
                         "(open in Perfetto)")
    ap.add_argument("--obs", action="store_true",
                    help="enable device-side approximation telemetry and "
                         "print the metric summary")
    args = ap.parse_args()

    if args.routed_demo:
        routed_demo(args.mode)
        return

    obs.configure(enabled=True, device_telemetry=args.obs,
                  trace_path=args.trace)
    obs.reset_tracer()

    cfg = get_config("gemma3-12b").replace(
        n_layers=6, d_model=128, n_heads=4, n_kv_heads=2, d_head=32, d_ff=256,
        vocab=1024, remat=False,
        approx=ApproxConfig(mode=args.mode, e_a=1e-4, omega=0.2,
                            attn_table=args.attn_table),
    )  # a local:global sliding-window model end to end
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    # staggered budgets: short and long requests mixed, so the static
    # scheduler visibly wastes decode steps that the continuous one refills
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, (int(n),)).astype(np.int32),
                    max_new_tokens=args.max_new if i % 2 == 0
                    else max(1, args.max_new // 4))
            for i, n in enumerate(rng.integers(5, 24, args.requests))]

    if args.scheduler == "continuous":
        engine = ContinuousEngine(model, params, args.batch, cache_len=128,
                                  temperature=args.temperature)
        t0 = time.time()
        results = engine.serve(reqs)
        dt = time.time() - t0
    else:
        engine = DecodeEngine(model, params, args.batch, cache_len=128,
                              temperature=args.temperature)
        t0 = time.time()
        results = serve_static(model, params, reqs, batch_size=args.batch,
                               cache_len=128, engine=engine)
        dt = time.time() - t0
    # throughput over tokens each request actually generated (Result.steps ==
    # len(tokens), trimmed at that request's own EOS/budget — padded or
    # post-EOS slots don't inflate the number)
    total = sum(r.steps for r in results)
    steady = max(dt - engine.compile_time_s, 1e-9)
    print(f"mode={args.mode}/{args.scheduler}: served {len(results)} requests "
          f"/ {total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s wall, "
          f"{total / steady:.1f} tok/s steady after "
          f"{engine.compile_time_s:.2f}s compile, CPU); "
          f"{engine.batch_steps:g} batch rounds, "
          f"wasted slot-step fraction {engine.wasted_fraction:.2f}")
    for i, r in enumerate(results[:3]):
        print(f"  req{i}: prompt={r.prompt_len} toks -> {r.tokens.tolist()}")
    if args.obs:
        import json

        print(json.dumps({"metrics": obs.get_registry().summary(),
                          "engine_metrics": engine.metrics.summary()},
                         indent=1, default=str))
    if args.trace:
        obs.get_tracer().save(args.trace, metadata={
            "metrics": {
                "histograms": engine.metrics.summary()["histograms"],
                "counters": obs.get_registry().summary()["counters"],
            }})
        print(f"trace written to {args.trace}")
    print("serve_decode OK")


if __name__ == "__main__":
    main()
