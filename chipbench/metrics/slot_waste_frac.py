"""Percent of slot-rounds (batch slots x sampling rounds) that yielded no
token for a live request: the engine's wasted_fraction over the window."""


def read(run):
    c = run["counters"]
    rounds = c["batch"] * c["batch_steps"]
    return 100.0 * c["wasted_slot_steps"] / rounds if rounds else None
