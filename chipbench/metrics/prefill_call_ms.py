"""Device milliseconds per prefill call: the trace's time in the prefill
executable (jit_prefill) over the prefill calls the engine counted."""

import devtrace


def read(run):
    tr, n = run["trace"], run["counters"]["prefills"]
    if tr is None or not n:
        return None
    t = devtrace.module_s(tr, "jit_prefill")
    return 1e3 * t / n if t else None
