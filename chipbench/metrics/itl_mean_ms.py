"""Window milliseconds per decode tick: the mean gap between the tokens of a
live slot, prefill stalls included."""


def read(run):
    c = run["counters"]
    ticks = c["batch_steps"] - c["prefills"]
    return 1e3 * run["window_s"] / ticks if ticks else None
