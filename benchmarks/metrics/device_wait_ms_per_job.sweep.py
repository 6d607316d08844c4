"""Time the worker's collect lane blocked until a group's result was
ready on the device (``worker.device_wait`` spans, summed over the
window) per job completed in the window, in milliseconds."""


def read(run):
    if not run.window_jobs:
        return None
    s = run.counters.get("dbx_span_seconds{span=worker.device_wait}:sum",
                         0.0)
    return 1e3 * s / run.window_jobs if s > 0 else None
