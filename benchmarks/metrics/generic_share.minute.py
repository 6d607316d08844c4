"""Share of the jobs the compute backend ran in the window that took the
generic (unfused) path, from ``dbx_route_jobs_total{route}``, in
percent. A program without the counter reports nothing."""

PREFIX = "dbx_route_jobs_total{"


def read(run):
    by_route = {k: v for k, v in run.counters.items()
                if k.startswith(PREFIX)}
    total = sum(by_route.values())
    if total <= 0:
        return None
    generic = sum(v for k, v in by_route.items()
                  if "route=generic" in k)
    return 100.0 * generic / total
