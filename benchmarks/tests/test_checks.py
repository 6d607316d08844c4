"""The comparison that decides ``correct`` fails when it should.

- The control: the plain reference put in the program's place and
  computed in bfloat16 (the precision below the configurations' float32)
  reads above every configuration's ``disagree_share`` limit.
- Faults planted under a whole run (the chip check skipped, the CPU's
  generic path as the timed path, tiny sizes): half of each batch left
  out, and every answer altered where it is produced, each turn
  ``correct`` false; the same run unbroken is correct.
"""

import copy
import time

import ml_dtypes
import numpy as np
import pytest

from harness import cell as cell_mod
from harness import check, runner, traffic


def _tiny(cell_entry):
    cell, config, tr = cell_mod.files(cell_entry)
    config = copy.deepcopy(config)
    tr = copy.deepcopy(tr)
    pairs = "pairs" in config["instruments"]
    config["instruments"] = {"tickers": 12, **({"pairs": 16} if pairs
                                              else {})}
    config["bars"] = 300
    config["grid"] = ("lookback=10:30:4,z_entry=0.5:2.5:0.5" if pairs
                      else "fast=3:7,slow=20:40:4")
    config["worker"]["jobs_per_chip"] = {"closed": 6, "open": 4}
    config["correct"]["sample_jobs"] = {"closed": 4, "open": 16}
    if tr["loop"] == "open":
        tr["grid"] = "fast=3:5,slow=20:28:4"
        tr["rate_per_s"] = 20
        tr["lead_in_s"] = 0.5
    else:
        tr["lead_in_s"] = 0.5
    return cell, config, tr


# The open loop has no cell in BENCHMARK.json while its stall is open
# (PERF.md, Open questions); its traffic file stays, and runs here.
SERVED = {"name": "sp500_daily_sma.served_small", "config": "sp500_daily_sma",
          "traffic": "served_small", "chips": 1}
CELLS = cell_mod.benchmark()["workloads"] + [SERVED]


def _name(cell_entry):
    return cell_entry["name"]


@pytest.mark.parametrize("cell_entry", CELLS, ids=_name)
def test_control_fails_the_limit(cell_entry):
    """bfloat16 in the program's place, at the cell's grid and bars, on
    three seeds: above the limit every time."""
    cell, config, tr = cell_mod.files(cell_entry)
    config = copy.deepcopy(config)
    config["instruments"] = dict(config["instruments"], tickers=40,
                                 **({"pairs": 3} if "pairs" in
                                    config["instruments"] else {}))
    family = cell_mod.reference(config["strategy"])
    for seed in (11, 2**31 + 7, 987654321):
        wl = traffic.Workload(config, tr, seed)
        tally = check.Tally()
        for inst in wl.instruments[:3]:
            legs = wl.legs(inst)
            ref = family.sweep(legs, wl.axes, cost=wl.cost,
                               periods_per_year=wl.ppy)
            with np.errstate(all="ignore"):
                ctl = family.sweep(legs, wl.axes, cost=wl.cost,
                                   periods_per_year=wl.ppy,
                                   dt=ml_dtypes.bfloat16)
            tally.add(ref, ctl)
        assert tally.share > config["correct"]["disagree_share"], seed


def test_a_win_either_way_agrees_a_trade_does_not():
    """A held bar whose return rounds to zero flips the win test of every
    cell long on it (1/active of hit rate): no changed decision. A
    changed trade moves the turnover and disagrees."""
    ref = np.zeros((9, 4))
    ref[6], ref[7], ref[8] = 0.52, 20.0, 40.0
    got = ref.copy()
    got[6] += 1.0 / 1000
    assert not check.disagreeing_cells(ref, got).any()
    got[7] += 1.0
    got[8] += 2.0
    assert check.disagreeing_cells(ref, got).all()


class _Wrapped:
    """The backend, with attribute reads and writes passed through."""

    def __init__(self, inner):
        object.__setattr__(self, "inner", inner)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __setattr__(self, name, value):
        setattr(self.inner, name, value)

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
    result block is scaled by 1.05."""

    def submit(self, jobs):
        return self.inner.submit(jobs)

    def collect(self, pending):
        from distributed_backtesting_exploration_tpu.rpc import wire

        out = self.inner.collect(pending)
        for c in out:
            m = wire.metrics_from_bytes(c.metrics)
            c.metrics = wire.metrics_to_bytes(
                m._replace(sortino=m.sortino * np.float32(1.05)))
        return out


def _run(cell_entry, wrap=None):
    from distributed_backtesting_exploration_tpu.rpc import compute

    cell, config, tr = _tiny(cell_entry)
    backend = compute.JaxSweepBackend()
    if wrap is not None:
        backend = wrap(backend)
    return runner.execute(cell, config, tr, seed=123456789012,
                          seconds=2.0, trace=False, t_process=time.time(),
                          require_chip=False, backend=backend)


@pytest.mark.parametrize("cell_entry", CELLS, ids=_name)
def test_sound_run_is_correct(cell_entry):
    line = _run(cell_entry)
    assert line["correct"], line["checks"]
    assert line["checks"]["disagree_share"]["value"] == 0.0
    assert set(line["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell_entry", CELLS, ids=_name)
@pytest.mark.parametrize("fault", [_HalfBatch, _Altered])
def test_planted_fault_is_not_correct(cell_entry, fault, monkeypatch):
    monkeypatch.setattr(runner, "DRAIN_WAIT_S", 3.0)
    line = _run(cell_entry, fault)
    assert not line["correct"], line["checks"]
