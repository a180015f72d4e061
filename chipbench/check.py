"""The comparison that decides ``correct``.

The window records the logits that the engine's own prefill and tick
executables return while they serve the last queue (``bench.Recorder``).
Once the window has closed, a sample of that queue's requests, drawn from the
seed and always holding the one with the most output, is run through the
float32 reference of the configuration's family (``families/<family>.py``
``logits``): the prompt row exactly as the engine prefilled it (left-padded to
the prefill width with the pad token) followed by the served tokens.
At each position that produced a served token two numbers are read:

* ``logit_gap``: how far the served token's reference logit lies below the
  reference's best.  With greedy decoding it is 0 where the program agrees
  with the reference and small where rounding flips a near tie; a token
  altered where it is produced opens it.
* ``logit_err``: the widest difference, over the vocabulary, between the
  program's logits and the reference's.  A forward pass computed in too low
  a precision opens it, whether or not that changes which token leads.

Each is the widest over the sample.  The control (``calibrate.py``) goes
through the same ``numbers`` and ``verdict``: the reference in a lower
precision takes the program's place, its logits as the program's and the
token it puts first as the served one.
"""

from __future__ import annotations

import numpy as np

import precision
import traffic

PAD_ID = 0  # the engine's pad token (ContinuousEngine's default)


def sample(done: list, seed: int, k: int) -> list:
    """Indices of ``k`` finished requests: the longest output, then a seeded
    draw of the rest.  ``done`` holds dicts with ``out`` (served tokens)."""
    longest = max(range(len(done)), key=lambda i: len(done[i]["out"]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(traffic.seed_words(seed) + [7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequence(prompt: np.ndarray, out: np.ndarray, width: int, total: int):
    """The engine's prefill row for ``prompt`` followed by the served tokens
    that were fed back, right-padded to ``total`` (causal, so the tail pad
    changes no earlier position)."""
    seq = np.full((total,), PAD_ID, np.int32)
    seq[width - len(prompt):width] = prompt
    seq[width:width + len(out) - 1] = out[:-1]
    return seq


def served_logits(params, cell: dict, r: dict,
                  dot=precision.f32_dot) -> np.ndarray:
    """The reference's logits (n, vocab) at the ``n`` positions that produced
    request ``r``'s served tokens, with the cell's prefill width and room for
    the mix's longest output."""
    mix = cell["traffic"]
    width = mix["prefill_len"]
    total = width + mix["output"]["max"] - 1
    seq = sequence(r["prompt"], r["out"], width, total)
    return np.asarray(cell["family"].logits(params, seq, cell["config"],
                                            width - 1, dot))[:len(r["out"])]


def numbers(ref: np.ndarray, got: np.ndarray, served: np.ndarray) -> dict:
    """The two numbers of one request: ``ref`` and ``got`` are (n, vocab)
    logits of the reference and of what stands in the program's place,
    ``served`` the (n,) tokens it served."""
    gap = ref.max(-1) - ref[np.arange(len(served)), served]
    return {"logit_gap": float(gap.max()),
            "logit_err": float(np.abs(got - ref).max())}


def stand_in(r: dict, ref: np.ndarray, name: str, vocab: int,
             low=None) -> tuple:
    """``(logits, served tokens)`` of what stands in the program's place:
    ``program`` itself, a control (the reference in a lower precision, whose
    logits ``low`` are given), or ``altered_token``, the program with each
    served token moved one id on where it is produced."""
    out = np.asarray(r["out"])
    got = r.get("logits")
    if name in precision.CONTROLS:
        return low, low.argmax(-1)
    if got is None or got.shape != ref.shape:
        got = np.full(ref.shape, np.finfo(np.float32).max)  # none recorded
    if name == "altered_token":
        return got, (out + 1) % vocab
    return got, out


def readings(params, cell: dict, picked: list,
             names: tuple = ("program",)) -> dict:
    """For each of ``names`` (see ``stand_in``), the widest of each number
    over the ``picked`` requests (dicts with ``prompt``, ``out`` and the
    program's ``logits``), all at the same served positions."""
    worst = {n: {"logit_gap": 0.0, "logit_err": 0.0} for n in names}
    vocab = cell["config"]["vocab_size"]
    for r in picked:
        ref = served_logits(params, cell, r)
        for n in names:
            low = (served_logits(params, cell, r, precision.CONTROLS[n])
                   if n in precision.CONTROLS else None)
            got, served = stand_in(r, ref, n, vocab, low)
            for k, v in numbers(ref, got, served).items():
                worst[n][k] = max(worst[n][k], v)
    return worst


def verdict(values: dict, limits: dict, n_failed: int) -> tuple:
    """``(correct, checks)``: each number the cell's limits name, beside its
    limit, and the requests that failed outright (limit 0)."""
    checks = {name: {"value": values[name], "limit": limit}
              for name, limit in limits.items()}
    checks["failed_requests"] = {"value": n_failed, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
