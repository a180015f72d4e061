"""90th percentile over all requests of the window of completion time minus
the time its queue was submitted.  A request that never finished has no
completion time and counts as the window's end."""

import numpy as np


def read(run):
    end = run["t0"] + run["window_s"]
    lat = [(r["done"] if r["done"] is not None else end) - r["submit"]
           for r in run["requests"]]
    return float(np.percentile(lat, 90))
