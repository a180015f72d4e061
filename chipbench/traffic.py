"""The one traffic generator: a mix file of parameters in, request queues out.

A mix (``chipbench/traffic/<name>.json``) of kind ``offline_queues`` is served
as successive closed queues of ``queue`` requests.  Every queue holds the same
multiset of prompt and output lengths: the ``queue`` mid-quantiles of each
length distribution, clipped to its range.  Queue ``k`` permutes them (prompt
and output lengths independently) by ``k`` alone, and the seed draws the token
ids.  With greedy decoding and no EOS the order of the lengths decides the
engine's schedule, so two seeds serve different tokens through the same
schedule: the seed changes the inputs, not the amount of work.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lengths(spec: dict, n: int) -> list:
    """The ``n`` mid-quantiles of a length distribution, clipped and rounded."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(round(min(spec["max"], max(spec["min"], v)))))
    return out


def seed_words(seed: int) -> list:
    """A seed of any size as 32-bit words, for numpy and JAX alike."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return [seed & 0xFFFFFFFF, seed >> 32]


def queue(mix: dict, seed: int, index: int, vocab: int) -> list:
    """Queue ``index`` of a run: ``[(prompt int32 array, max_new_tokens)]``."""
    if mix["kind"] != "offline_queues":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    n = mix["queue"]
    order = np.random.default_rng(index)
    prompts = order.permutation(lengths(mix["prompt"], n))
    outputs = order.permutation(lengths(mix["output"], n))
    rng = np.random.default_rng(seed_words(seed) + [index])
    if prompts.max() > mix["prefill_len"]:
        raise ValueError("a prompt is longer than the mix's prefill_len")
    if mix["prefill_len"] + outputs.max() - 1 > mix["cache_len"]:
        raise ValueError("prefill_len + the longest output overflows cache_len")
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]
