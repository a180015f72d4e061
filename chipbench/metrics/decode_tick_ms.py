"""Device milliseconds per decode tick: the trace's time in the tick
executable (jit_tick) over the ticks the engine counted."""

import devtrace


def read(run):
    tr, c = run["trace"], run["counters"]
    ticks = c["batch_steps"] - c["prefills"]
    if tr is None or not ticks:
        return None
    t = devtrace.module_s(tr, "jit_tick")
    return 1e3 * t / ticks if t else None
