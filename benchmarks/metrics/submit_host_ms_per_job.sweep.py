"""Host time of the compute route's submit (decode, transfer, launch;
``dbx_kernel_submit_seconds{phase=execute}``, summed over the window)
per job completed in the window, in milliseconds. Launch is
asynchronous: this is host time, not kernel time."""


def read(run):
    if not run.window_jobs:
        return None
    s = sum(v for k, v in run.counters.items()
            if k.startswith("dbx_kernel_submit_seconds{")
            and k.endswith("phase=execute}:sum"))
    return 1e3 * s / run.window_jobs if s > 0 else None
