"""Packing of the copied results: one result block per job and its
handoff to the worker's control thread (``worker.pack`` spans, summed
over the window) per job completed in the window, in milliseconds."""


def read(run):
    if not run.window_jobs:
        return None
    s = run.counters.get("dbx_span_seconds{span=worker.pack}:sum", 0.0)
    return 1e3 * s / run.window_jobs if s > 0 else None
