"""Panels delivered again after their first delivery, per job completed
in the window: the worker's ``FetchPayload`` recoveries of digest-only
jobs (``dbx_worker_payload_fetches_total``) plus the device page pool's
page misses (``dbx_page_pool_misses_total``, every field). About 0 when
the universe stays resident on the worker and on the device."""


def read(run):
    if not run.window_jobs:
        return None
    fetches = run.counters.get("dbx_worker_payload_fetches_total", 0)
    misses = sum(v for k, v in run.counters.items()
                 if k.startswith("dbx_page_pool_misses_total{"))
    return (fetches + misses) / run.window_jobs
