"""chip_smoke.py's drivers, rehearsed on the CPU at a tiny size.

The script itself refuses to run off the chip; these tests call its
driver functions with interpret-mode kernels (``use_fused=True`` on the
CPU) so the served path, the route and failure checks and the reference
comparison are exercised before any chip time is spent.
"""

import pytest

import chip_smoke
from distributed_backtesting_exploration_tpu.rpc import compute

TINY_GRIDS = {
    "bollinger": "window=3;5,k=1.0;2.0",
    "momentum": "lookback=2;4",
    "pairs": "lookback=5;8,z_entry=1.0;2.0",
}
TINY = dict(tickers=3, bars=48, family_tickers=2, family_grids=TINY_GRIDS,
            ragged_bars=(44, 40), ragged_tickers=2,
            north_star_grid="fast=2:5,slow=8:12:2",
            scenarios=2, sample=3, jobs_per_chip=64, timeout_s=300.0,
            backend_kwargs={"use_fused": True})


def test_smoke_driver_end_to_end():
    report = chip_smoke.run_smoke(**TINY)
    # North star, 2 per family, 2 ragged-length jobs for each of SMA,
    # Bollinger and pairs, the scenario base and 2 scenarios.
    assert report["jobs"] == 3 + 2 * len(TINY_GRIDS) + 2 * 3 + 1 + 2
    assert set(report["verify"]) == {"sma_crossover", "scenario",
                                     *TINY_GRIDS}
    assert all(not r["over_budget"] for r in report["verify"].values())
    # One-chip default routes: the paged fused kernels and the scenario
    # megakernel.
    assert set(report["routes"]["sma_crossover"]) == {"paged", "scenario"}
    assert report["jax_compile"]["compile_s"] > 0


def test_smoke_driver_fails_loudly_on_backend_exception(monkeypatch):
    """An exception in the backend fails the smoke at once — it used to
    end as a clean exit after idle polls, the jobs left to their lease."""
    def boom(self, jobs):
        raise RuntimeError("injected backend failure")

    monkeypatch.setattr(compute.JaxSweepBackend, "submit", boom)
    monkeypatch.setattr(compute.JaxSweepBackend, "process", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="failed in the "
                                                      "backend"):
        chip_smoke.run_smoke(**{**TINY, "timeout_s": 60.0})


def test_mesh_smoke_driver(monkeypatch, devices):
    """The --chips 4 path on the virtual CPU mesh: the mesh worker and a
    job past the (test-lowered) fused bar cap on the time-sharded route,
    each result spanning every device."""
    monkeypatch.setattr(compute.JaxSweepBackend, "_FUSED_MAX_BARS", 64)
    report = chip_smoke.run_mesh_smoke(
        tickers=8, bars=48, long_bars=96, north_star_grid="fast=2:5,"
        "slow=8:12:2", long_grid="fast=2;4,slow=6;8", sample=8,
        jobs_per_chip=16, timeout_s=300.0, n_devices=len(devices),
        backend_kwargs={"use_fused": True})
    assert report["result_device_counts"] == [len(devices)]
    assert "timeshard" in report["routes"]["sma_crossover"]
    assert not report["verify"]["sma_crossover"]["over_budget"]
