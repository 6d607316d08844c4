"""Copy of the ready results from the device to the host (``worker.d2h``
spans, summed over the window) per job completed in the window, in
milliseconds."""


def read(run):
    if not run.window_jobs:
        return None
    s = run.counters.get("dbx_span_seconds{span=worker.d2h}:sum", 0.0)
    return 1e3 * s / run.window_jobs if s > 0 else None
