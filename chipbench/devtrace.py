"""Profiler trace: capture a window, load it, and reduce it to numbers.

``load`` turns the profiler's ``.xplane.pb`` into a small dict of plain
events (name, start ns, duration ns), which is what the reductions and the
recorded test trace use:

* ``ops`` / ``modules``: per device plane, the events of its "XLA Ops" and
  "XLA Modules" lines (one module event per executable run).  An op is named
  by its HLO instruction (``%fusion.140``), and a Mosaic kernel's name gets
  its custom-call target: ``%_call.9[tpu_custom_call]``.  Ops nest: a
  ``while`` op's event spans the events of its body's ops;
* ``host``: the harness's own ``TraceAnnotation`` spans (names starting with
  ``bench.``), which label what the host was doing;
* ``window``: start and end of the ``bench.window`` span, the traced window.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from contextlib import contextmanager

import jax

DEVICE_PREFIX = "/device:TPU:"
MOSAIC = 'custom_call_target="tpu_custom_call"'
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"


@contextmanager
def capture(logdir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}: {paths}")
    data = ProfileData.from_file(paths[0])
    out = {"ops": {}, "modules": {}, "host": [], "window": None}
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX) and line.name in (
                    OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                out[key].setdefault(plane.name, []).extend(
                    [op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                    for e in line.events)
            elif not plane.name.startswith("/device:"):
                for e in line.events:
                    if e.name.startswith("bench."):
                        ev = [e.name, int(e.start_ns), int(e.duration_ns)]
                        if e.name == WINDOW:
                            out["window"] = ev[1:]
                        else:
                            out["host"].append(ev)
    return out


def op_name(hlo: str) -> str:
    """``%name`` of an HLO instruction's text, ``[tpu_custom_call]`` added
    for a Mosaic kernel; a module's name is kept whole."""
    name = hlo.split(" = ", 1)[0]
    return name + "[tpu_custom_call]" if MOSAIC in hlo else name


def read_saved(path: str) -> dict:
    """``load``'s dict, kept as gzipped JSON (the recorded test trace)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ------------------------------ reductions ------------------------------------


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def window_s(tr: dict) -> float:
    return tr["window"][1] / 1e9


def busy_intervals(tr: dict, plane: str) -> list:
    """Merged [start, end) ns intervals in which an op ran on ``plane``."""
    lo = tr["window"][0]
    hi = lo + tr["window"][1]
    spans = sorted((a, b) for _, a, b in _clip(tr["ops"][plane], lo, hi))
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_s(tr: dict) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    planes = list(tr["ops"])
    if not planes:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(tr, p)) for p in planes) \
        / len(planes) / 1e9


def _named(tr: dict, key: str, match) -> float:
    lo = tr["window"][0]
    hi = lo + tr["window"][1]
    total = 0
    for evs in tr[key].values():
        total += sum(b - a for n, a, b in _clip(evs, lo, hi) if match(n))
    return total / len(tr[key]) / 1e9 if tr[key] else 0.0


def module_s(tr: dict, jit_name: str) -> float:
    """Device seconds of the executable ``jit_name`` (e.g. ``jit_tick``),
    averaged over the device planes."""
    return _named(tr, "modules", lambda n: n == jit_name
                  or n.startswith(jit_name + "("))


def op_s(tr: dict, match) -> float:
    """Device seconds of the ops whose name satisfies ``match``, averaged
    over the device planes."""
    return _named(tr, "ops", match)


def pack_kernel(name: str) -> bool:
    """The pack value kernel: the Mosaic call of table_pack_lookup's jitted
    ``_call`` (``%_call.N``; the fused value+slope kernel is ``%_call_grad``)."""
    return name.startswith("%_call.") and name.endswith("[tpu_custom_call]")


def leaf_ops(events: list) -> list:
    """The events that hold no other: ops that run, not loops around them."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][1] >= e[1] + e[2]]


def top_ops(tr: dict, n: int = 10) -> list:
    """The ``n`` leaf ops that took most device time, each named
    ``<executable>/<op>`` by the module event around it."""
    lo = tr["window"][0]
    hi = lo + tr["window"][1]
    total: dict = {}
    planes = max(1, len(tr["ops"]))
    for plane, evs in tr["ops"].items():
        mods = sorted(tr["modules"].get(plane, []), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, a, b in _clip(leaf_ops(evs), lo, hi):
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][0].split("(")[0] if i >= 0 and \
                a < mods[i][1] + mods[i][2] else "outside modules"
            key = f"{mod}/{name}"
            total[key] = total.get(key, 0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / planes / 1e9] for name, ns in ranked]


def idle_gaps(tr: dict, n: int = 10) -> list:
    """The ``n`` longest device-idle gaps of the first device plane, each
    labelled by the innermost harness span around its middle."""
    if not tr["ops"]:
        return []
    lo = tr["window"][0]
    hi = lo + tr["window"][1]
    busy = busy_intervals(tr, sorted(tr["ops"])[0])
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        around = [(d, name) for name, s, d in tr["host"] if s <= mid < s + d]
        label = min(around)[1] if around else "outside bench spans"
        out.append([label, (b - a) / 1e9])
    return out
