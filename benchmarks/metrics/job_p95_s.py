"""95th percentile over every job due in the window of (completion
recorded by the dispatcher - the job's due time on the open-loop
schedule). A job that never completes counts as missing the limit."""

import math

import numpy as np


def read(run):
    if run.loop != "open" or run.latencies is None or not run.latencies.size:
        return None
    lat = np.sort(run.latencies)
    v = float(lat[max(math.ceil(0.95 * lat.size) - 1, 0)])
    return v if math.isfinite(v) else None
