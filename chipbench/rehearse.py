"""Compile a cell's two engine executables for a described TPU v5e, no chip.

  JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell> [--layers 8 10 12]

For each depth it compiles the ``ContinuousEngine``'s prefill (one admitted
row, addressed by its slot) and decode tick at the cell's batch, prefill width
and cache length from shapes alone, with the model that the configuration's
family makes, and prints ``memory_analysis()`` with the peak the serve loop
would hold: the larger executable plus the two caches that stay alive beside
it (the engine's zero cache and the last prefill's).  What the compiler
refuses here costs no chip time.  Nothing runs, so no time is measured.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import bench  # noqa: E402

GB = 1e9


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def executable_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def sizes(cell: dict, n_layers: int, device) -> dict:
    from repro.models import build_model
    from repro.serving.engine import ContinuousEngine

    mix = cell["traffic"]
    B, S, W = mix["batch"], mix["prefill_len"], mix["cache_len"]
    config = dict(cell["config"], num_hidden_layers=n_layers)
    model = build_model(cell["family"].program_config(config))
    sh = SingleDeviceSharding(device)
    spec = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sh)
    params = jax.tree.map(spec, model.abstract_params())
    cache = jax.tree.map(spec, model.abstract_cache(B, W))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sh)
    exe = ContinuousEngine(model, params, B, W)._executables
    prefill = exe["prefill"].lower(params, {"tokens": i32(B, S),
                                            "slot": i32()}, cache).compile()
    tick = exe["decode_step"].lower(params, i32(B, 1), i32(B), cache).compile()
    out = {"params": tree_bytes(params), "cache": tree_bytes(cache),
           "prefill": executable_bytes(prefill),
           "decode_step": executable_bytes(tick),
           "kernels": tick.as_text().count("tpu_custom_call")}
    out["serve_peak"] = max(out["prefill"], out["decode_step"]) \
        + 2 * out["cache"]
    return out


def main() -> int:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, nargs="+")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    # the kernels choose compiled or interpreted from the default backend,
    # which is the CPU here: compile them as they would be on the chip
    jax.default_backend = lambda: "tpu"
    cell = bench.load_cell(args.workload, trace=False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for n in args.layers or [cell["config"]["num_hidden_layers"]]:
        s = sizes(cell, n, topo.devices[0])
        print(f"{args.workload} layers={n}: params {s['params'] / GB:.3f} GB, "
              f"one cache {s['cache'] / GB:.3f} GB, prefill "
              f"{s['prefill'] / GB:.3f} GB, decode tick "
              f"{s['decode_step'] / GB:.3f} GB, serve peak "
              f"{s['serve_peak'] / GB:.3f} GB; tick holds {s['kernels']} "
              f"tpu_custom_call", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
