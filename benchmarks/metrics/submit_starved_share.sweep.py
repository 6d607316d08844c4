"""Share of the window in which the worker's compute thread waited for
a batch from the control thread (``worker.wait_batch`` spans,
``dbx_span_seconds`` summed over the window), in percent: the submit
side starved of work."""


def read(run):
    s = run.counters.get("dbx_span_seconds{span=worker.wait_batch}:sum", 0.0)
    return 100.0 * s / run.seconds if s > 0 and run.seconds > 0 else None
