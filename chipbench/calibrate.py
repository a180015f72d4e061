"""Readings that a cell's limits are set from, and its control.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 3 ...

In one process (set-up and compiles are paid once), for each seed: weights
from the seed, one queue of the cell's mix served through the timed path
(``bench.window``, which records the program's logits), then the check's
sample of its requests compared with the float32 reference of the
configuration's family through the comparison that decides a run's
``correct`` (``check.readings`` and ``check.verdict``, with the cell's
limits).  The controls are the family's reference with its weight products in
a lower precision (``precision.CONTROLS``).  It prints per seed, for each of

* ``program``: the program's readings, the lower end of each limit;
* ``fp8``: the control, the reference computed in float8 e4m3 in the
  program's place; its smallest reading sets the upper end;
* ``int8``: the same in int8, for the record;
* ``altered_token``: the program with each served token moved one id on
  where it is produced, the fault that ``logit_gap`` is there to catch;

``logit_gap``, ``logit_err`` and ``correct`` as a run would judge it.  The
benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import bench  # noqa: E402
import check  # noqa: E402
import weights  # noqa: E402

STAND_INS = ("program", "fp8", "int8", "altered_token")


def readings(served: bench.Served, cell: dict, seed: int) -> dict:
    """One seed: a queue through the timed path, then every stand-in's
    numbers and verdict."""
    engine, scale = served.engine, cell["family"].scale
    engine.params = None
    gc.collect()
    engine.params = weights.make(served.shapes, seed, scale)
    win = bench.window(served, cell, seed, 0.0, None)
    picked = bench.picked_with_logits(win, cell, seed)
    engine.params = None
    gc.collect()
    n_failed = bench.failed(win["requests"], cell["config"]["vocab_size"])
    params = weights.make(served.shapes, seed, scale)
    got = check.readings(params, cell, picked, STAND_INS)
    out = {"failed": n_failed, "tokens": sum(len(r["out"]) for r in picked)}
    for name, values in got.items():
        correct, _ = check.verdict(values, cell["limits"], n_failed)
        out[name] = {**values, "correct": correct}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = bench.load_cell(args.workload, trace=False)
    bench.find_chip(cell["workload"]["chips"])
    bench.use_compile_cache()
    served = bench.build(cell, args.seeds[0])
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(served, cell, seed)
        r.update(seed=seed, seconds=round(time.perf_counter() - t, 1))
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
