"""The dispatcher's ``CompleteJobs`` handler (``dbx_rpc_seconds``,
summed over the window) per job completed in the window, in
milliseconds."""


def read(run):
    if not run.window_jobs:
        return None
    s = run.counters.get("dbx_rpc_seconds{method=CompleteJobs}:sum", 0.0)
    return 1e3 * s / run.window_jobs if s > 0 else None
