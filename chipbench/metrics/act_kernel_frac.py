"""Percent of the device's busy time spent in the table_pack activation
kernel (the Pallas pack value kernel's ops in the trace)."""

import devtrace


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    busy, k = devtrace.busy_s(tr), devtrace.op_s(tr, devtrace.pack_kernel)
    return 100.0 * k / busy if busy and k else None
