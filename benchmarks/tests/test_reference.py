"""The plain float64 references agree with the system's own generic path
(``models/`` + ``parallel/sweep.py``) at small sizes, on all nine
metrics: a mismatch in cost, periods per year or a metric's definition
shows here before it shows on the chip."""

import importlib

import numpy as np
import pytest

from harness import universe


def _panel(seed, n, bars):
    cfg = {"bars": bars, "periods_per_year": 252,
           "instruments": {"tickers": n},
           "prices": universe.DEFAULT_PRICES}
    return universe.closes(cfg, seed)


def _generic_sma(close, axes, cost):
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    c = jnp.asarray(close, jnp.float32)
    panel = data.OHLCV(c, c, c, c, c)
    m = sweep.jit_sweep(panel, base.get_strategy("sma_crossover"),
                        sweep.product_grid(**axes), cost=cost)
    return np.stack([np.asarray(f, np.float64) for f in m], axis=1)


def _generic_pairs(y, x, axes, cost):
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import pairs
    from distributed_backtesting_exploration_tpu.parallel import sweep

    m = pairs.run_pairs_sweep(jnp.asarray(y, jnp.float32),
                              jnp.asarray(x, jnp.float32),
                              sweep.product_grid(**axes), cost=cost)
    return np.stack([np.asarray(f, np.float64) for f in m], axis=1)


def _agree(ref, got, family):
    from harness import check

    flags = check.disagreeing_cells(ref, got)
    # f32 against float64: a knife-edge decision may differ in a cell or
    # two; everything else agrees within the comparison's tolerances.
    assert flags.mean() <= 0.02, (family, flags.mean())
    ok = ~flags
    for k in range(9):
        np.testing.assert_allclose(got[k][ok], ref[k][ok], rtol=2e-3,
                                   atol=2e-3, err_msg=f"{family} row {k}")


def test_sma_reference_matches_generic_path():
    ref_mod = importlib.import_module("reference.sma_crossover")
    close = _panel(3, 4, 300)
    axes = {"fast": np.arange(3, 9, dtype=np.float32),
            "slow": np.arange(20, 60, 4, dtype=np.float32)}
    got = _generic_sma(close, axes, 1e-3)
    for i in range(close.shape[0]):
        ref = ref_mod.sweep([{"close": close[i]}], axes, cost=1e-3,
                            periods_per_year=252)
        _agree(ref, got[i], "sma")


def test_pairs_reference_matches_generic_path():
    ref_mod = importlib.import_module("reference.pairs")
    close = _panel(5, 6, 260)
    y, x = close[:3], close[3:]
    axes = {"lookback": np.arange(10, 30, 4, dtype=np.float32),
            "z_entry": np.arange(0.5, 3.0, 0.5, dtype=np.float32)}
    got = _generic_pairs(y, x, axes, 1e-3)
    for i in range(3):
        ref = ref_mod.sweep([{"close": y[i]}, {"close": x[i]}], axes,
                            cost=1e-3, periods_per_year=252)
        _agree(ref, got[i], "pairs")


@pytest.mark.parametrize("family", ["sma_crossover", "pairs"])
def test_work_counts_are_positive_and_scale(family):
    mod = importlib.import_module(f"reference.{family}")
    axes = ({"fast": np.arange(5, 25), "slow": np.arange(30, 230, 2)}
            if family == "sma_crossover" else
            {"lookback": np.arange(10, 110, 2),
             "z_entry": np.arange(0.5, 5.5, 0.5)})
    ops1, b1 = mod.work(1, 1260, axes)
    ops2, b2 = mod.work(2, 1260, axes)
    assert ops1 > 0 and b1 > 0
    assert ops2 == 2 * ops1 and b2 == 2 * b1
