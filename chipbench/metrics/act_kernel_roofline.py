"""Percent of its roofline the activation kernel reaches.  The least time is
bound by bytes: every activation element read and written once in bfloat16
at the chip's HBM bandwidth.  Elements are counted by the configuration's
family (``act_elements``) from shapes and the engine's counters, so the count
is of the activation's work whatever implements it."""

import devtrace

# The activation reads and writes each element once, in the model's bfloat16.
BYTES_PER_ELEMENT = 2 + 2


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    t = devtrace.op_s(tr, devtrace.pack_kernel)
    if not t:
        return None
    n = run["family"].act_elements(run["config"], run["counters"])
    least = n * BYTES_PER_ELEMENT / run["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / t
