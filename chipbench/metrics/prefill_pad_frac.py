"""Percent of the prefill rows' tokens that are padding: 1 - admitted prompt
tokens / (prefill calls x batch x prefill width)."""


def read(run):
    c = run["counters"]
    rows = c["prefills"] * c["batch"] * c["prefill_len"]
    if not rows:
        return None
    admitted = sum(len(r["prompt"]) for r in run["requests"])
    return 100.0 * (1.0 - admitted / rows)
