"""Percent of its roofline the activation kernel reaches.  The least time is
bound by bytes: every activation element read and written once in bfloat16
(flops.ACT_BYTES_PER_ELEMENT) at the chip's HBM bandwidth.  Elements are
counted from shapes and the engine's counters, so the count is of the
activation's work whatever implements it."""

import devtrace
import flops


def read(run):
    tr, c = run["trace"], run["counters"]
    if tr is None:
        return None
    t = devtrace.op_s(tr, devtrace.pack_kernel)
    if not t:
        return None
    n = flops.act_elements(run["config"], c["prefills"],
                           c["batch_steps"] - c["prefills"], c["batch"],
                           c["prefill_len"])
    least = n * flops.ACT_BYTES_PER_ELEMENT / run["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / t
