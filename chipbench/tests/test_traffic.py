"""The generator: the same seed gives the same inputs, and every seed the
same schedule of lengths."""

import json
from pathlib import Path

import numpy as np

import traffic

MIX = json.loads((Path(__file__).parents[1] / "traffic" /
                  "prefill_long.json").read_text())
BIG = 2**33 + 5


def test_same_seed_same_inputs():
    a, b = traffic.queue(MIX, BIG, 1, 50304), traffic.queue(MIX, BIG, 1, 50304)
    assert all((p == q).all() and m == n for (p, m), (q, n) in zip(a, b))


def test_seeds_change_tokens_not_lengths():
    a = traffic.queue(MIX, BIG, 2, 50304)
    b = traffic.queue(MIX, BIG + 2**32, 2, 50304)  # differs above 32 bits
    assert [(len(p), m) for p, m in a] == [(len(p), m) for p, m in b]
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))


def test_queues_hold_the_mix():
    q0, q1 = traffic.queue(MIX, 1, 0, 100), traffic.queue(MIX, 1, 1, 100)
    assert sorted(len(p) for p, _ in q0) == sorted(len(p) for p, _ in q1)
    assert [len(p) for p, _ in q0] != [len(p) for p, _ in q1]
    for key in ("prompt", "output"):
        spec = MIX[key]
        got = traffic.lengths(spec, MIX["queue"])
        assert spec["min"] <= min(got) and max(got) <= spec["max"]
        assert abs(float(np.median(got)) - spec["median"]) \
            <= 0.05 * spec["median"]
