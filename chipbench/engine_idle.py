"""Device-idle time put down to the serve loop's phases.

  python3 chipbench/engine_idle.py --workload <cell> --seeds <n> ... \\
      [--seconds 51] [--gap 0.5]

Run by hand on the chip.  In one process (set-up is paid once) it profiles,
for each seed, a whole window of the cell as a run without ``--trace`` serves
it (``bench.window`` inside ``devtrace.capture``), and prints one JSON line
per window: its seconds, the device's busy and idle seconds, the idle split
below, the idle seconds under each engine span, and each idle gap of at least
``--gap`` seconds with the innermost span around its middle (the rule of
``devtrace.idle_gaps``).

The split: each device-idle instant of the window, on the first device
plane, goes to the innermost engine span (``ENGINE_SPANS`` of
``repro.serving.engine``, the engine's ``jax.profiler.TraceAnnotation``
names) that covers it.  Under a wait span (``WAIT_SPANS``) the host waits on
the device and the device does nothing: ``wait``.  Under any other engine
span the device waits on host work: ``host_bound``.  Outside every engine
span it is the harness's share: ``outside``.  The three add up to the
plane's idle time.  A program without the engine spans gives ``None``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import devtrace  # noqa: E402


def engine_spans():
    """The program's engine span names and its wait spans, or ``None`` for a
    program that has none."""
    try:
        from repro.serving.engine import ENGINE_SPANS, WAIT_SPANS
    except ImportError:
        return None
    return set(ENGINE_SPANS), set(WAIT_SPANS)


def load(logdir: str) -> dict:
    """``devtrace.load``'s dict with the engine's spans added to ``host``
    and, alone, under ``engine``."""
    from jax.profiler import ProfileData

    tr = devtrace.load(logdir)
    names = (engine_spans() or (set(), set()))[0]
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    tr["engine"] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                    for plane in ProfileData.from_file(path).planes
                    if not plane.name.startswith("/device:")
                    for line in plane.lines for e in line.events
                    if e.name in names]
    tr["host"] += tr["engine"]
    return tr


def innermost(spans: list, lo: int, hi: int) -> list:
    """``[a, b, name]`` pieces tiling ``[lo, hi)``, each named by the
    innermost of the properly nested ``spans`` ([name, start, duration])
    that covers it, ``None`` where none does."""
    out, stack, t = [], [], lo

    def upto(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            name, end = stack.pop()
            if end > t:
                out.append([t, end, name])
                t = end
        if x > t:
            out.append([t, x, stack[-1][0] if stack else None])
            t = x

    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        if s + d <= lo or s >= hi:
            continue
        upto(max(s, lo))
        stack.append((name, min(s + d, hi)))
    upto(hi)
    return out


def idle_by_span(tr: dict) -> dict:
    """Idle seconds of the window on the first device plane, by the
    innermost engine span that covers them (``None``: outside every one)."""
    lo = tr["window"][0]
    hi = lo + tr["window"][1]
    busy = devtrace.busy_intervals(tr, sorted(tr["ops"])[0])
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out: dict = {}
    pieces = innermost(tr["engine"], lo, hi)
    k = 0
    for a, b in idle:
        while pieces[k][1] <= a:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < b:
            pa, pb, name = pieces[j]
            ns = min(b, pb) - max(a, pa)
            out[name] = out.get(name, 0) + ns
            j += 1
    return {name: ns / 1e9 for name, ns in out.items()}


def split(tr: dict) -> dict | None:
    """Seconds of device-idle time that are ``host_bound``, ``wait`` and
    ``outside``, and ``idle`` in all, over the traced window."""
    spans = engine_spans()
    if spans is None or not tr["ops"] or not tr.get("engine"):
        return None
    by = idle_by_span(tr)
    out = {"host_bound": 0.0, "wait": 0.0,
           "outside": by.get(None, 0.0), "idle": sum(by.values())}
    for name, s in by.items():
        if name is not None:
            out["wait" if name in spans[1] else "host_bound"] += s
    return out


def profile(served, cell: dict, seed: int, seconds: float,
            gap: float) -> dict:
    """One whole window of the cell under the profiler, reduced."""
    import bench

    logdir = tempfile.mkdtemp(prefix="chipbench-idle-")
    try:
        with devtrace.capture(logdir):
            win = bench.window(served, cell, seed, seconds, None)
        win.pop("recorder")
        tr = load(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    tokens = sum(len(r["out"]) for r in win["requests"]
                 if r["out"] is not None)
    by = idle_by_span(tr) if tr["ops"] and tr["engine"] else {}
    return {"seed": seed, "queues": win["queues"],
            "queue_s": [round(q, 4) for q in win["queue_s"]],
            "output_tokens_per_s": tokens / win["window_s"],
            "window_s": devtrace.window_s(tr), "busy_s": devtrace.busy_s(tr),
            "split": split(tr), "engine_spans": len(tr["engine"]),
            "idle_by_span": {str(k): v for k, v in
                             sorted(by.items(), key=lambda kv: -kv[1])},
            "gaps": [g for g in devtrace.idle_gaps(tr, 10**6) if g[1] >= gap]}


def main() -> int:
    import bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--gap", type=float, default=0.5,
                    help="report idle gaps at least this long (s)")
    args = ap.parse_args()
    cell = bench.load_cell(args.workload, trace=False)
    bench.find_chip(cell["workload"]["chips"])
    bench.use_compile_cache()
    served = bench.build(cell, args.seeds[0])
    for seed in args.seeds:
        print(json.dumps(profile(served, cell, seed, args.seconds,
                                 args.gap)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
