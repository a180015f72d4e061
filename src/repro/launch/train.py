"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --reduced \\
      --steps 200 --batch 8 --seq 128

Full-size runs use the production mesh (requires real TPU devices); --reduced
shrinks the config for CPU-scale end-to-end runs (the quickstart path).  On a
real multi-host cluster this same entry point runs per host after
``jax.distributed.initialize()`` (env-driven; no code changes).
"""

from __future__ import annotations

import argparse
import time

import jax

from repro import obs
from repro.approx import TABLE_MODES
from repro.launch.compile_cache import use_compile_cache
from repro.models import ShapeSpec, build_model, get_config
from repro.optim import adamw
from repro.train.loop import TrainConfig, run


def reduced_config(cfg):
    from tests.test_archs import reduced  # single source of truth for shrink rules

    return reduced(cfg.name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="family-preserving shrink for CPU-scale runs")
    ap.add_argument("--mesh", choices=["none", "debug", "prod", "multipod"],
                    default="none")
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
                    help="write a Chrome-trace JSON of the run (train.step / "
                         "train.ckpt / design-phase spans; open in Perfetto, "
                         "validate with tools/check_trace.py)")
    ap.add_argument("--obs", action="store_true",
                    help="enable device-side approximation telemetry and "
                         "print the metric summary")
    args = ap.parse_args()
    use_compile_cache()

    obs.configure(enabled=True, device_telemetry=args.obs,
                  trace_path=args.trace)
    obs.reset_tracer()
    obs.reset_registry()

    cfg = get_config(args.arch)
    if args.reduced:
        import sys, os

        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
        cfg = reduced_config(cfg)
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

    mesh = None
    if args.mesh == "debug":
        from repro.launch.mesh import make_debug_mesh

        n = len(jax.devices())
        mesh = make_debug_mesh(max(1, n // 2), min(2, n))
    elif args.mesh in ("prod", "multipod"):
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))

    # mesh before model: build_model pre-places sharded approx packs over it,
    # so the activation closures capture per-core slices (no step-0 reshard)
    model = build_model(cfg, mesh=mesh)

    shape = ShapeSpec("cli", seq_len=args.seq, global_batch=args.batch,
                      kind="train")
    tc = TrainConfig(
        steps=args.steps, ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        accum=args.accum,
        opt=adamw.AdamWConfig(lr=args.lr, warmup_steps=max(1, args.steps // 20),
                              total_steps=args.steps),
    )
    t0 = time.perf_counter()
    out = run(model, shape, tc, mesh=mesh)
    wall = time.perf_counter() - t0
    steps_done = len(out["losses"])
    steady = max(wall - out["compile_time_s"], 1e-9)
    print(f"done: step={out['final_step']} "
          f"loss {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f} "
          f"stragglers={out['stragglers']} preempted={out['preempted']}; "
          f"{steps_done / wall:.2f} step/s wall, {steps_done / steady:.2f} "
          f"step/s steady after {out['compile_time_s']:.2f}s compile")
    if args.obs:
        import json

        print(json.dumps(obs.get_registry().summary(), indent=1,
                         default=str))
    if args.trace:
        obs.get_tracer().save(args.trace, metadata={
            "summary": {"steps": steps_done, "wall_s": wall,
                        "compile_time_s": out["compile_time_s"]},
            "metrics": obs.get_registry().summary()})
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
