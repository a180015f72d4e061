"""Serving launcher: batched prefill+decode over a synthetic request queue.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --reduced \\
      --requests 8 --max-new 16

``--scheduler continuous`` (default) serves through the ContinuousEngine
(admission queue, per-slot budgets/EOS/RNG, mid-stream slot refill);
``--scheduler static`` keeps the fixed-group baseline.

ScopeKit (docs/observability.md): ``--trace PATH`` writes a Perfetto-loadable
Chrome trace of the run (request lifecycles, admission/decode phase spans, jit-compile
events) with the engine's metric summary embedded; ``--obs`` additionally
enables device-side approximation telemetry and prints the metric summary.
The launcher always records host-side spans, so throughput is reported both
wall-clock and steady-state (compile time excluded).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import obs
from repro.approx import TABLE_MODES
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model, get_config
from repro.serving.engine import (ContinuousEngine, DecodeEngine, Request,
                                  serve_static)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduler", default="continuous",
                    choices=["continuous", "static"],
                    help="continuous = admission queue + mid-stream slot "
                         "refill; static = fixed request groups")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--approx-mode",
                    choices=["exact", *TABLE_MODES],
                    default=None,
                    help="nonlinearity backend; table_pack = one fused "
                         "multi-function pack + kernel for the whole network, "
                         "quant_pack = the same pack with int8/int16 entries "
                         "dequantized on read, poly_pack = the Pareto-planned "
                         "pack (degree-1..3 Horner cells, mixed widths; see "
                         "--pack-budget), routed_* = the same packs with "
                         "dynamic per-row fn_id dispatch (one executable for "
                         "every member), sharded_pack = the pack's values "
                         "split over the mesh 'model' axis (per-shard base "
                         "rebasing, psum combine)")
    ap.add_argument("--approx-ea", type=float, default=None,
                    help="override the config's error budget E_a")
    ap.add_argument("--pack-shards", type=int, default=None,
                    help="sharded_pack modes: split the pack values this many "
                         "ways (distributes when a mesh binds a matching "
                         "'model' axis; otherwise a stacked-shard sum)")
    ap.add_argument("--pack-budget", type=int, default=None,
                    help="poly_pack modes: total-bytes budget for the design-"
                         "space planner (greedy member downgrade until the "
                         "pack fits; default keeps each function's Pareto-"
                         "cheapest candidate)")
    ap.add_argument("--rope-table", action="store_true",
                    help="serve rotary embeddings from the pack's folded trig"
                         " members (any table mode; docs/range_reduction.md)")
    ap.add_argument("--attn-table", action="store_true",
                    help="TableFlash: serve flash attention's softmax exponent"
                         " from the pack's exp_neg member (any table mode; "
                         "docs/table_flash.md)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the run (open in "
                         "Perfetto; validate with tools/check_trace.py)")
    ap.add_argument("--obs", action="store_true",
                    help="enable device-side approximation telemetry "
                         "(out-of-domain clamps, quant saturation, routed "
                         "dispatch) and print the metric summary")
    args = ap.parse_args()
    use_compile_cache()

    # host-side spans are always on for the launcher (they never touch the
    # device computation); device telemetry only with --obs, and only then is
    # the model built with instrumented activation closures
    obs.configure(enabled=True, device_telemetry=args.obs,
                  trace_path=args.trace)
    obs.reset_tracer()

    cfg = get_config(args.arch)
    if args.reduced:
        import os, sys

        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
        from tests.test_archs import reduced

        cfg = reduced(args.arch)
    if (args.approx_mode is not None or args.approx_ea is not None
            or args.pack_shards is not None or args.pack_budget is not None
            or args.rope_table or args.attn_table):
        import dataclasses

        # override only what was passed; keep the config's other approx params
        kw = {}
        if args.approx_mode is not None:
            kw["mode"] = args.approx_mode
        if args.approx_ea is not None:
            kw["e_a"] = args.approx_ea
        if args.pack_shards is not None:
            kw["pack_shards"] = args.pack_shards
        if args.pack_budget is not None:
            kw["pack_budget"] = args.pack_budget
        if args.rope_table:
            kw["rope_table"] = True
        if args.attn_table:
            kw["attn_table"] = True
        cfg = cfg.replace(approx=dataclasses.replace(cfg.approx, **kw))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, (int(n),)).astype(np.int32),
                    max_new_tokens=args.max_new)
            for n in rng.integers(4, 32, args.requests)]
    if args.scheduler == "continuous":
        engine = ContinuousEngine(model, params, args.batch, args.cache_len,
                                  temperature=args.temperature)
        t0 = time.time()
        results = engine.serve(reqs)
    else:
        engine = DecodeEngine(model, params, args.batch, args.cache_len,
                              temperature=args.temperature)
        t0 = time.time()
        results = serve_static(model, params, reqs, batch_size=args.batch,
                               cache_len=args.cache_len, engine=engine)
    dt = time.time() - t0
    total_new = sum(r.steps for r in results)  # per-request trimmed counts
    steady = max(dt - engine.compile_time_s, 1e-9)
    print(f"served {len(results)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s wall, "
          f"{total_new / steady:.1f} tok/s steady after "
          f"{engine.compile_time_s:.2f}s compile, {args.scheduler}); "
          f"{engine.batch_steps:g} batch rounds, wasted slot-step fraction "
          f"{engine.wasted_fraction:.2f}")
    for i, r in enumerate(results[:4]):
        print(f"  req{i}: prompt_len={r.prompt_len} steps={r.steps} "
              f"-> {r.tokens[:8].tolist()}...")
    summary = {"requests": len(results), "tokens": total_new,
               "wall_s": dt, "compile_time_s": engine.compile_time_s,
               "tok_s_wall": total_new / dt, "tok_s_steady": total_new / steady,
               "scheduler": args.scheduler}
    if args.obs:
        print(json.dumps({"metrics": obs.get_registry().summary(),
                          "engine_metrics": engine.metrics.summary()},
                         indent=1, default=str))
    if args.trace:
        obs.get_tracer().save(args.trace, metadata={
            "summary": summary,
            "metrics": {
                # engine-owned latency histograms + the global (device
                # telemetry) registry merged for the report CLI
                "histograms": engine.metrics.summary()["histograms"],
                "counters": obs.get_registry().summary()["counters"],
            }})
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
