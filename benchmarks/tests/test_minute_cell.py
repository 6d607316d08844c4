"""The minute-bar VWAP cell (``sp500_minute_vwap.sweep``): its plain
reference against the system's generic path, the reference's blocked and
unblocked forms, its readers on synthetic runs, a whole tiny run on the
CPU that reads correct, and not correct with either planted fault, and
the preflight that stops a program which cannot run the cell."""

import copy
import importlib
import time
from types import SimpleNamespace

import numpy as np
import pytest

from harness import cell as cell_mod
from harness import check, runner, universe

CELL = "sp500_minute_vwap.sweep"


def _ref():
    return importlib.import_module("reference.vwap_reversion")


def _panel(seed, n, bars):
    cfg = {"bars": bars, "periods_per_year": 98280,
           "instruments": {"tickers": n},
           "prices": universe.DEFAULT_PRICES}
    close = universe.closes(cfg, seed)
    fields = universe.ohlcv(cfg, seed, close)
    return close, np.stack([f["volume"] for f in fields])


def _generic_vwap(close, volume, axes, cost, ppy):
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    c = jnp.asarray(close, jnp.float32)
    panel = data.OHLCV(c, c, c, c, jnp.asarray(volume, jnp.float32))
    # The wire's column order: the grid's axes by name (k, window).
    m = sweep.jit_sweep(panel, base.get_strategy("vwap_reversion"),
                        sweep.product_grid(**dict(sorted(axes.items()))),
                        cost=cost,
                        periods_per_year=ppy)
    return np.stack([np.asarray(f, np.float64) for f in m], axis=1)


def test_reference_matches_generic_path():
    close, volume = _panel(7, 3, 400)
    axes = {"window": np.arange(10, 60, 10, dtype=np.float32),
            "k": np.arange(0.5, 2.5, 0.5, dtype=np.float32)}
    got = _generic_vwap(close, volume, axes, 1e-3, 98280)
    for i in range(close.shape[0]):
        ref = _ref().sweep([{"close": close[i], "volume": volume[i]}],
                           axes, cost=1e-3, periods_per_year=98280)
        flags = check.disagreeing_cells(ref, got[i])
        assert flags.mean() <= 0.02, flags.mean()
        assert (ref[8] > 0).any()          # some cells trade
        ok = ~flags
        for k in range(9):
            np.testing.assert_allclose(got[i][k][ok], ref[k][ok],
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"row {k}")


@pytest.mark.parametrize("block", [1, 2, 3])
def test_blocked_reference_equals_unblocked(block):
    close, volume = _panel(11, 1, 500)
    axes = {"window": np.arange(10, 80, 10, dtype=np.float32),
            "k": np.arange(0.5, 3.0, 0.5, dtype=np.float32)}
    legs = [{"close": close[0], "volume": volume[0]}]
    whole = _ref().sweep(legs, axes, cost=1e-3, periods_per_year=98280,
                         block_windows=None)
    part = _ref().sweep(legs, axes, cost=1e-3, periods_per_year=98280,
                        block_windows=block)
    assert part.shape == (9, 7 * 5)
    np.testing.assert_array_equal(part, whole)


def test_work_counts_scale():
    axes = {"window": np.arange(10, 400, 10), "k": np.arange(0.5, 4.5, .125)}
    ops1, b1 = _ref().work(1, 98280, axes)
    ops2, b2 = _ref().work(2, 98280, axes)
    assert ops1 > 0 and ops2 == 2 * ops1 and b2 == 2 * b1
    assert b1 == 98280 * 4 * 2 + 1248 * 9 * 4


def _trace(op_s, module_s, window_s=50.0, busy_s=40.0):
    from harness import trace

    return trace.Reduction(window_s=window_s, devices=["/device:TPU:0"],
                           busy_s=busy_s, module_s=module_s, op_s=op_s,
                           collective_exposed_s=0.0, idle_gaps=[])


def test_prep_share_reads_the_time_outside_the_kernel():
    read = cell_mod.reader("vwap_prep_share.minute")
    tr = _trace({"jit__fused_vwap_call/vwap_kernel.1": 6.0,
                 "jit__fused_vwap_call/reduce-window.3": 3.0,
                 "jit__fused_vwap_call/fusion.7": 1.0,
                 "jit__paged_gather/fusion": 5.0},
                {"jit__fused_vwap_call": 10.0, "jit__paged_gather": 5.0})
    assert read(SimpleNamespace(trace=tr)) == pytest.approx(40.0)
    # A program without the kernel, or no trace: nothing.
    assert read(SimpleNamespace(trace=_trace({}, {}))) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_roofline_reads_the_vwap_programs():
    read = cell_mod.reader("vwap_fused_roofline")
    axes = {"window": np.arange(10, 400, 10), "k": np.arange(0.5, 4.5, .125)}
    fam = _ref()
    tr = _trace({}, {"jit__fused_vwap_call": 10.0, "jit__fused_call": 99.0})
    run = SimpleNamespace(
        trace=tr, config={"strategy": "vwap_reversion"},
        device_kind="TPU v5 lite",
        work=lambda: fam.work(1000, 98280, axes))
    ops, _ = fam.work(1000, 98280, axes)
    assert read(run) == pytest.approx(100.0 * ops / 197e12 / 10.0)
    run.config = {"strategy": "sma_crossover"}
    assert read(run) is None


def test_refetch_counts_fetches_and_page_misses_per_job():
    read = cell_mod.reader("panel_refetch_per_job.minute")
    counters = {"dbx_worker_payload_fetches_total": 3,
                "dbx_page_pool_misses_total{field=close}": 4,
                "dbx_page_pool_misses_total{field=volume}": 3,
                "dbx_page_pool_hits_total{field=close}": 900}
    run = SimpleNamespace(counters=counters, window_jobs=50)
    assert read(run) == pytest.approx(0.2)
    assert read(SimpleNamespace(counters={}, window_jobs=50)) == 0.0
    assert read(SimpleNamespace(counters=counters, window_jobs=0)) is None


def test_generic_share_reads_the_route_counter():
    read = cell_mod.reader("generic_share.minute")
    run = SimpleNamespace(counters={
        "dbx_route_jobs_total{route=paged}": 90,
        "dbx_route_jobs_total{route=generic}": 10,
        "dbx_route_jobs_total{route=fused}": 0})
    assert read(run) == pytest.approx(10.0)
    run.counters["dbx_route_jobs_total{route=generic}"] = 0
    assert read(run) == 0.0
    # The parent program has no such counter: nothing to report.
    assert read(SimpleNamespace(counters={})) is None


def test_idle_share_reads_the_trace():
    read = cell_mod.reader("device_idle_share.minute")
    run = SimpleNamespace(trace=_trace({}, {}, window_s=50.0, busy_s=40.0))
    assert read(run) == pytest.approx(20.0)
    assert read(SimpleNamespace(trace=None)) is None


def test_the_cell_is_declared_with_its_metrics():
    b = cell_mod.benchmark()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "sweep"
    e2e = {m["name"] for m in cell_mod.metric_specs(CELL, trace=False)}
    assert e2e == {"backtests_per_s", "setup_s"}
    per_layer = {m["name"] for m in cell_mod.metric_specs(CELL, trace=True)}
    assert per_layer == {"vwap_fused_roofline", "vwap_prep_share.minute",
                         "panel_refetch_per_job.minute",
                         "generic_share.minute", "device_idle_share.minute"}
    _, config, _ = cell_mod.workload(CELL)
    assert config["bars"] == 98280 and config["reduced"] == []
    assert config["correct"]["sample_jobs"]["closed"] == 8


class _Wrapped:
    """The backend, with attribute reads and writes passed through."""

    def __init__(self, inner):
        object.__setattr__(self, "inner", inner)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __setattr__(self, name, value):
        setattr(self.inner, name, value)

    def submit(self, jobs):
        return self.inner.submit(jobs)

    def collect(self, pending):
        return self.inner.collect(pending)


class _HalfBatch(_Wrapped):
    """Leaves out the second half of every batch of the timed phase (the
    warm-up's jobs, ``warm...``, pass untouched)."""

    def submit(self, jobs):
        jobs = list(jobs)
        if any(not j.id.startswith("warm") for j in jobs):
            jobs = jobs[:(len(jobs) + 1) // 2]
        return self.inner.submit(jobs)


class _Altered(_Wrapped):
    """Alters every answer where it is produced: the Sortino row of each
    result block scaled by 1.05."""

    def collect(self, pending):
        from distributed_backtesting_exploration_tpu.rpc import wire

        out = self.inner.collect(pending)
        for c in out:
            m = wire.metrics_from_bytes(c.metrics)
            c.metrics = wire.metrics_to_bytes(
                m._replace(sortino=m.sortino * np.float32(1.05)))
        return out


def _tiny_run(wrap=None):
    from distributed_backtesting_exploration_tpu.rpc import compute

    cell, config, tr = cell_mod.workload(CELL)
    config = copy.deepcopy(config)
    tr = dict(tr, lead_in_s=0.5)
    config["instruments"] = {"tickers": 12}
    config["bars"] = 400
    config["grid"] = "window=10:50:10,k=0.5:2.5:0.5"
    config["worker"]["jobs_per_chip"] = {"closed": 6, "open": 4}
    config["correct"]["sample_jobs"] = {"closed": 4, "open": 16}
    backend = compute.JaxSweepBackend()
    if wrap is not None:
        backend = wrap(backend)
    return runner.execute(cell, config, tr, seed=3000000077, seconds=2.0,
                          trace=False, t_process=time.time(),
                          require_chip=False, backend=backend)


def test_tiny_run_is_correct():
    line = _tiny_run()
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) >= {"setup_s", "backtests_per_s"}


@pytest.mark.parametrize("fault", [_HalfBatch, _Altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_tiny_run_with_a_planted_fault_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(runner, "DRAIN_WAIT_S", 3.0)
    line = _tiny_run(fault)
    assert not line["correct"], line["checks"]
    if fault is _HalfBatch:
        assert line["checks"]["missing"]["value"] > 0, line["checks"]


def test_preflight_passes_this_program():
    from harness import preflight

    preflight.require_uncapped("vwap_reversion", 98280)


def test_preflight_stops_a_program_that_demotes_the_cell(monkeypatch):
    from distributed_backtesting_exploration_tpu.rpc import compute
    from harness import preflight

    monkeypatch.setattr(compute, "bar_capped",
                        lambda strategy, bars, cap=None: True)
    with pytest.raises(SystemExit, match="generic path"):
        preflight.require_uncapped("vwap_reversion", 98280)


def test_preflight_stops_a_program_without_the_cap_test(monkeypatch):
    from distributed_backtesting_exploration_tpu.rpc import compute
    from harness import preflight

    monkeypatch.delattr(compute, "bar_capped")
    with pytest.raises(SystemExit, match="no router cap test"):
        preflight.require_uncapped("vwap_reversion", 98280)
