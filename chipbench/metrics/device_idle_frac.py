"""Percent of the traced window in which no op ran on the device."""

import devtrace


def read(run):
    tr = run["trace"]
    if tr is None or not tr["ops"]:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(tr) / devtrace.window_s(tr))
