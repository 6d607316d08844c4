"""The readers of the result path's phases (the program's own spans and
the dispatcher's ``CompleteJobs`` handler), on synthetic counter deltas
as ``serve.counter_deltas`` leaves them: a histogram's sum delta under
``<key>:sum``."""

from types import SimpleNamespace

import pytest

from harness import cell as cell_mod

PER_JOB = {
    "device_wait_ms_per_job.sweep": "dbx_span_seconds{span=worker.device_wait}",
    "d2h_ms_per_job.sweep": "dbx_span_seconds{span=worker.d2h}",
    "pack_ms_per_job.sweep": "dbx_span_seconds{span=worker.pack}",
    "report_ms_per_job.sweep": "dbx_span_seconds{span=worker.report}",
    "complete_ms_per_job.sweep": "dbx_rpc_seconds{method=CompleteJobs}",
}
STARVED = "submit_starved_share.sweep"
WAIT_BATCH = "dbx_span_seconds{span=worker.wait_batch}"


def _run(counters, *, jobs=200, seconds=50.0):
    return SimpleNamespace(counters=counters, window_jobs=jobs,
                           seconds=seconds)


@pytest.mark.parametrize("name,key", sorted(PER_JOB.items()))
def test_per_job_reader_divides_the_window_sum_by_the_jobs(name, key):
    read = cell_mod.reader(name)
    # 0.5 s over 200 jobs = 2.5 ms/job; other keys do not count.
    counters = {key: 50, key + ":sum": 0.5,
                "dbx_span_seconds{span=worker.collect}:sum": 9.0}
    assert read(_run(counters)) == pytest.approx(2.5)


@pytest.mark.parametrize("name,key", sorted(PER_JOB.items()))
def test_per_job_reader_is_none_without_jobs_or_time(name, key):
    read = cell_mod.reader(name)
    assert read(_run({key + ":sum": 0.5}, jobs=0)) is None
    assert read(_run({key + ":sum": 0.0})) is None
    # A program without the span or the handler's histogram: nothing.
    assert read(_run({})) is None


def test_starved_share_is_wait_over_the_window():
    read = cell_mod.reader(STARVED)
    assert read(_run({WAIT_BATCH + ":sum": 12.5})) == pytest.approx(25.0)
    # The share is of the window, so it does not need completed jobs.
    assert read(_run({WAIT_BATCH + ":sum": 12.5}, jobs=0)) == \
        pytest.approx(25.0)


def test_starved_share_is_none_without_waits():
    read = cell_mod.reader(STARVED)
    assert read(_run({WAIT_BATCH + ":sum": 0.0})) is None
    assert read(_run({})) is None
    assert read(_run({WAIT_BATCH + ":sum": 1.0}, seconds=0.0)) is None


def test_the_phase_metrics_are_declared_for_both_sweeps():
    specs = {m["name"]: m for m in cell_mod.benchmark()["per_layer"]}
    for name in list(PER_JOB) + [STARVED]:
        m = specs[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "backtests_per_s"
        assert m["workloads"] == ["sp500_daily_sma.sweep",
                                  "pairs_daily_ols.sweep"]
