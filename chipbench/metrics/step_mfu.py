"""Useful model FLOPs of the window's served requests per window second, as
a percent of the chips' bf16 peak.  Useful work is the configuration's
family's count (``request_flops``): it counts logical heads, unpadded prompt
tokens and served tokens, so padding, padded heads and wasted slot rows do
not count."""


def read(run):
    flops = run["family"].request_flops
    work = sum(flops(run["config"], len(r["prompt"]), len(r["out"]))
               for r in run["requests"] if r["out"] is not None)
    peak = run["peak"]["bf16_flops"] * run["chips"]
    return 100.0 * work / run["window_s"] / peak
