"""Worker-side payload decode (``dbx_compute_decode_seconds``, summed
over the window) per job completed in the window, in milliseconds."""


def read(run):
    if not run.window_jobs:
        return None
    s = run.counters.get("dbx_compute_decode_seconds:sum", 0.0)
    return 1e3 * s / run.window_jobs if s > 0 else None
