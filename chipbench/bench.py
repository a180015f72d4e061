"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric is a file
that this module finds by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<workload>.json`` and
``metrics/<metric>.py``, under this directory.  The architecture comes from the
family that the configuration file names, ``families/<family>.py`` (see
``family``): the program's configuration, the reference, the weights' scales
and the counts of useful work.  No other module knows an architecture's keys
or leaves.

Set-up builds the model that the family makes of the configuration, makes its
weights on the device from the seed (``weights.py``), builds one
``ContinuousEngine`` with ``prefill_len`` fixed to the mix's prefill width,
and warms both of its executables with one short queue, plus the slot
scatter at every number of slots one admission can fill.  The window submits
successive queues of the mix to ``engine.serve``; it starts no queue after
``seconds`` and ends when the last one drains.  With ``trace`` the window is
the first queue alone, under the profiler.  After the window the peak device
memory is read, the program's state freed, and ``check.py`` compares a sample
of the served tokens with the float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path

import jax
import numpy as np

import check
import devtrace
import traffic
import weights

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------- the cell ------------------------------------


def load_module(path: Path, name: str):
    """The Python file at ``path``, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str):
    """The architecture module ``families/<name>.py``.  It holds

    * ``program_config(config)``: the program's ``ArchConfig`` for the
      configuration file ``config``, at the depth (and share of experts) the
      file states;
    * ``logits(params, tokens, config, first, dot)``: the plain float32
      reference, logits (T - first, vocab) of ``tokens`` (T,), with every
      weight product through ``dot`` (``precision.py``);
    * ``scale(names, shape)``: the init scale of the weight leaf at the path
      of keys ``names``, or ``None`` for a norm gain (ones);
    * ``request_flops(config, prompt_len, n_out)``: useful FLOPs of one
      served request (``step_mfu``);
    * ``act_elements(config, counters)``: elements the MLP activation is
      applied to in a window with the engine counters ``counters``
      (``act_kernel_roofline``);
    * ``COUNTERS``: engine attributes that ``window`` records in the run's
      ``counters`` beside the common ones."""
    return load_module(HERE / "families" / f"{name}.py",
                       "chipbench_family_" + name)


def load_config(name: str) -> tuple:
    """``(config, family module)``: the configuration file
    ``configs/<name>.json`` and the family it names.  A file that names no
    family, or one that has no module, is refused."""
    path = HERE / "configs" / f"{name}.json"
    config = load_json(path)
    where = path.relative_to(REPO)
    fam = config.get("family")
    if not isinstance(fam, str) or not fam.isidentifier():
        raise SystemExit(f"{where} names no family: it needs \"family\", the "
                         "name of a module in chipbench/families/")
    if not (HERE / "families" / f"{fam}.py").is_file():
        raise SystemExit(f"{where} names the family {fam!r}, which has no "
                         f"module chipbench/families/{fam}.py")
    return config, family(fam)


def load_cell(workload: str, trace: bool) -> dict:
    bench = load_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind]
               if workload in m.get("workloads", [workload])]
    config, fam = load_config(w["config"])
    return {
        "workload": w,
        "config": config,
        "family": fam,
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "limits": load_json(HERE / "limits" / f"{workload}.json"),
        "metrics": metrics,
    }


def find_chip(want: int) -> dict:
    """The accelerator this run measures; exits if there is none to measure."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"jax.devices()[0] is {d.platform!r}, not a TPU: "
                         "nothing to measure")
    if len(devs) < want:
        raise SystemExit(f"{len(devs)} chip(s); the cell needs {want}")
    peaks = load_json(HERE / "peaks.json")
    if d.device_kind not in peaks:
        raise SystemExit(f"device kind {d.device_kind!r} is not in peaks.json")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "peak": peaks[d.device_kind], "device": d}


# ------------------------------- set-up --------------------------------------


class CompileCounter:
    """Counts traces and backend compiles from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


@dataclasses.dataclass
class Served:
    engine: object
    shapes: object  # the program's parameter shapes


def build(cell: dict, seed: int) -> Served:
    from repro.models import build_model
    from repro.serving.engine import ContinuousEngine

    mix, fam = cell["traffic"], cell["family"]
    model = build_model(fam.program_config(cell["config"]))
    shapes = model.abstract_params()
    engine = ContinuousEngine(model, weights.make(shapes, seed, fam.scale),
                              mix["batch"], mix["cache_len"], temperature=0.0,
                              prefill_len=mix["prefill_len"])
    warm(engine)
    return Served(engine, shapes)


def warm(engine) -> None:
    """Compile what the window runs: the prefill and the decode tick through
    one short queue, and the slot scatter for 1..B admitted slots."""
    from repro.serving.engine import Request, scatter_cache_slots

    engine.serve([Request(prompt=np.arange(1, 9, dtype=np.int32),
                          max_new_tokens=2) for _ in range(2)])
    for n in range(1, engine.B + 1):
        jax.block_until_ready(scatter_cache_slots(
            engine._fresh, engine._fresh, range(n), engine._axes))


# ------------------------------- window --------------------------------------


class Recorder:
    """Holds the logits that the engine's own prefill and tick executables
    return while the current queue is served, with the prompt rows each
    prefill took, as device arrays: inside the window nothing is copied or
    waited for.  ``attach`` reads them after the window has closed."""

    def __init__(self, engine):
        self.engine = engine
        self.prefill, self.tick = engine._prefill, engine._tick
        self.events: list = []  # (prefill rows or None for a tick, logits)

    def __enter__(self):
        def prefill(params, batch, cache):
            logits, rcache = self.prefill(params, batch, cache)
            self.events.append((batch["tokens"], logits))
            return logits, rcache

        def tick(*args):
            out = self.tick(*args)
            self.events.append((None, out[1]))
            return out

        self.engine._prefill, self.engine._tick = prefill, tick
        return self

    def __exit__(self, *exc):
        self.engine._prefill, self.engine._tick = self.prefill, self.tick

    def attach(self, picked: list, width: int, vocab: int) -> None:
        """Give each picked request of the recorded queue ``logits`` (n,
        vocab): the program's at the positions that produced its n served
        tokens, the prefill's row of its slot and then that row of each
        following tick.  A request found in no prefill row gets none."""
        admitted = {}
        for e, (rows, _) in enumerate(self.events):
            if rows is not None:
                for j, row in enumerate(np.asarray(rows)):
                    admitted.setdefault(row.tobytes(), (e, j))
        ticks = [e for e, (rows, _) in enumerate(self.events) if rows is None]
        host: dict = {}

        def logits(e):
            if e not in host:
                lg = np.asarray(self.events[e][1])
                host[e] = lg.reshape(lg.shape[0], -1)[:, :vocab]
            return host[e]

        for r in picked:
            row = np.full((width,), check.PAD_ID, np.int32)
            row[width - len(r["prompt"]):] = r["prompt"]
            found = admitted.get(row.tobytes())
            if found is None:
                continue
            e, j = found
            after = [t for t in ticks if t > e][:len(r["out"]) - 1]
            r["logits"] = np.stack([logits(t)[j] for t in [e] + after]
                                   ).astype(np.float32)


def window(served: Served, cell: dict, seed: int, seconds: float,
           trace_dir: str | None) -> dict:
    from repro.serving.engine import Request

    engine, mix = served.engine, cell["traffic"]
    vocab = cell["config"]["vocab_size"]
    engine.reset_counters()
    done: list = []
    queue_s: list = []
    ann = jax.profiler.TraceAnnotation
    recorder = Recorder(engine)
    with devtrace.capture(trace_dir) if trace_dir else nullcontext(), \
            recorder:
        t0 = time.perf_counter()
        with ann(devtrace.WINDOW):
            k = 0
            while k == 0 or (trace_dir is None
                             and time.perf_counter() - t0 < seconds):
                with ann("bench.queue_build"):
                    q = traffic.queue(mix, seed, k, vocab)
                    recs = [{"prompt": p, "budget": b, "out": None,
                             "done": None} for p, b in q]
                    reqs = [Request(prompt=p, max_new_tokens=b) for p, b in q]
                    recorder.events = []

                def on_result(i, res, recs=recs):
                    recs[i]["done"] = time.perf_counter()
                    recs[i]["out"] = res.tokens

                submit = time.perf_counter()
                with ann("bench.serve"):
                    engine.serve(reqs, on_result=on_result)
                with ann("bench.results"):
                    for r in recs:
                        r["submit"] = submit
                    done.extend(recs)
                    queue_s.append(time.perf_counter() - submit)
                k += 1
        t1 = time.perf_counter()
    return {"t0": t0, "window_s": t1 - t0, "queues": k, "queue_s": queue_s,
            "requests": done,
            "last_queue": recs, "recorder": recorder,
            "counters": {"prefills": engine.prefills,
                         "batch_steps": engine.batch_steps,
                         "wasted_slot_steps": engine.wasted_slot_steps,
                         "refills": engine.refills,
                         "batch": engine.B,
                         "prefill_len": engine.prefill_len,
                         **{name: getattr(engine, name)
                            for name in cell["family"].COUNTERS}},
            "executables": engine.compile_counts()}


def picked_with_logits(win: dict, cell: dict, seed: int) -> list:
    """The check's sample of the window's last queue, each request with the
    program's logits at its served positions; frees the recorded ones."""
    mix = cell["traffic"]
    finished = [r for r in win["last_queue"]
                if r["out"] is not None and len(r["out"])]
    picked = [finished[i] for i in check.sample(finished, seed,
                                                mix["check_sample"])]
    win.pop("recorder").attach(picked, mix["prefill_len"],
                               cell["config"]["vocab_size"])
    return picked


def failed(requests: list, vocab: int) -> int:
    """Requests with no answer, the wrong number of tokens, or a token
    outside the vocabulary."""
    bad = 0
    for r in requests:
        out = r["out"]
        if out is None or len(out) != r["budget"] or \
                (len(out) and (out.min() < 0 or out.max() >= vocab)):
            bad += 1
    return bad


# ------------------------------- metrics -------------------------------------


def metric_reader(name: str):
    return load_module(HERE / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name).read


def read_metrics(cell: dict, run: dict) -> dict:
    out = {}
    for m in cell["metrics"]:
        value = metric_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# --------------------------------- run ---------------------------------------


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, chip: dict,
             t_start: float) -> dict:
    """One run; returns the result object the last line of output holds."""
    config = cell["config"]
    compiles = CompileCounter()
    served = build(cell, seed)
    setup_s = time.perf_counter() - t_start
    before = compiles.n
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        win = window(served, cell, seed, seconds, trace_dir)
        in_window = compiles.n - before
        tr = devtrace.load(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = chip["device"].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    requests = win["requests"]
    log(f"window: {win['queues']} queue(s), {len(requests)} requests in "
        f"{win['window_s']:.3f} s (queues "
        f"{', '.join(f'{q:.3f}' for q in win['queue_s'])} s); "
        f"compiles in window: {in_window}; "
        f"executables {win['executables']}; counters {win['counters']}")

    picked = picked_with_logits(win, cell, seed)
    shapes = served.shapes
    del served
    gc.collect()

    t_check = time.perf_counter()
    n_failed = failed(requests, config["vocab_size"])
    params = weights.make(shapes, seed, cell["family"].scale)
    values = check.readings(params, cell, picked)["program"]
    del params
    correct, checks = check.verdict(values, cell["limits"], n_failed)
    log(f"check: {len(picked)} requests, "
        f"{sum(len(r['out']) for r in picked)} served tokens against the "
        f"float32 reference in {time.perf_counter() - t_check:.1f} s")

    run = {"config": config, "family": cell["family"],
           "traffic": cell["traffic"], "peak": chip["peak"],
           "chips": chip["count"], "setup_s": setup_s, "trace": tr, **win}
    result = {"correct": bool(correct), "attempted": len(requests),
              "failed": n_failed, "metrics": read_metrics(cell, run),
              "device": {"platform": chip["platform"], "kind": chip["kind"],
                         "count": chip["count"],
                         "memory_peak_bytes": peak_bytes}}
    if tr is not None:
        result["device"]["busy_s"] = devtrace.busy_s(tr)
        result["device"]["window_s"] = devtrace.window_s(tr)
        result["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                               "idle_gaps": devtrace.idle_gaps(tr)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"{name} {c['value']} limit {c['limit']}")
    return result


def use_compile_cache() -> None:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set), for every
    executable however fast it compiles, so that set-up after a cell's first
    run in a checkout loads and compiles nothing."""
    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(args.workload, bool(args.trace))
    chip = find_chip(cell["workload"]["chips"])
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), chip,
                      t_start)
    print(json.dumps(result), flush=True)
    return 0
