"""95th percentile of the dispatcher's ``job.queue_wait`` span (enqueue
to the worker's take) over the jobs due in the window."""

import math

import numpy as np


def read(run):
    if not run.queue_waits:
        return None
    w = np.sort(np.asarray(run.queue_waits))
    return float(w[max(math.ceil(0.95 * w.size) - 1, 0)])
