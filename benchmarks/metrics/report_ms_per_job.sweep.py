"""The worker's completion report (``worker.report`` spans, summed over
the window: the request's build, compression, transport, the
dispatcher's handler and the reply) per job completed in the window, in
milliseconds."""


def read(run):
    if not run.window_jobs:
        return None
    s = run.counters.get("dbx_span_seconds{span=worker.report}:sum", 0.0)
    return 1e3 * s / run.window_jobs if s > 0 else None
