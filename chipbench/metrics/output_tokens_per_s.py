"""Tokens served to all requests finished in the window, per window second."""


def read(run):
    tokens = sum(len(r["out"]) for r in run["requests"] if r["out"] is not None)
    return tokens / run["window_s"]
