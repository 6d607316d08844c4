"""Band-machine cells that walk the history in T blocks, the router that
admits them past the one-block bar cap, and the page pool and host cache
sized from the device.

On the CPU the kernels run in interpret mode; a small static ``t_block``
stands in for the 2,048-bar blocks a long history takes on the chip.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_backtesting_exploration_tpu import obs
from distributed_backtesting_exploration_tpu.models.base import get_strategy
from distributed_backtesting_exploration_tpu.ops import fused
from distributed_backtesting_exploration_tpu.parallel import sweep
from distributed_backtesting_exploration_tpu.rpc import (
    backtesting_pb2 as pb, compute, page_pool, wire)
from distributed_backtesting_exploration_tpu.utils import data

BLOCK = 128


def _vwap(panel, grid, lens, **kw):
    return fused.fused_vwap_sweep(
        panel.close, panel.volume, np.asarray(grid["window"]),
        np.asarray(grid["k"]), t_real=lens, cost=1e-3, **kw)


def _bollinger(panel, grid, lens, **kw):
    return fused.fused_bollinger_sweep(
        panel.close, np.asarray(grid["window"]), np.asarray(grid["k"]),
        t_real=lens, cost=1e-3, **kw)


def _touch(panel, grid, lens, **kw):
    return fused.fused_bollinger_touch_sweep(
        panel.close, np.asarray(grid["window"]), np.asarray(grid["k"]),
        t_real=lens, cost=1e-3, **kw)


def _rsi(panel, grid, lens, **kw):
    return fused.fused_rsi_sweep(
        panel.close, np.asarray(grid["period"]), np.asarray(grid["band"]),
        t_real=lens, cost=1e-3, **kw)


def _stochastic(panel, grid, lens, **kw):
    return fused.fused_stochastic_sweep(
        panel.close, panel.high, panel.low, np.asarray(grid["window"]),
        np.asarray(grid["band"]), t_real=lens, cost=1e-3, **kw)


def _keltner(panel, grid, lens, **kw):
    return fused.fused_keltner_sweep(
        panel.close, panel.high, panel.low, np.asarray(grid["window"]),
        np.asarray(grid["k"]), t_real=lens, cost=1e-3, **kw)


# Every family in ops.fused.T_BLOCKED_FAMILIES: its wrapper, a small grid,
# and the tolerance its fused-vs-generic test in test_fused.py holds
# (Keltner's in-prep EMA ladder rounds apart from the generic scan).
TIGHT = dict(rtol=2e-4, atol=2e-5)
FAMILIES = {
    "vwap_reversion": (_vwap, dict(window=[10.0, 20.0, 40.0],
                                   k=[0.5, 1.0, 2.0]), TIGHT),
    "bollinger": (_bollinger, dict(window=[10.0, 25.0], k=[0.5, 1.5]),
                  TIGHT),
    "bollinger_touch": (_touch, dict(window=[10.0, 20.0], k=[1.0, 2.0]),
                        TIGHT),
    "rsi": (_rsi, dict(period=[7.0, 14.0], band=[15.0, 25.0]), TIGHT),
    "stochastic": (_stochastic, dict(window=[10.0, 21.0],
                                     band=[20.0, 30.0]), TIGHT),
    "keltner": (_keltner, dict(window=[10.0, 21.0], k=[1.5, 2.5]),
                dict(rtol=2e-3, atol=2e-4)),
}


def test_every_t_blocked_family_is_tested_here():
    """The router takes any history length for exactly these families
    (`rpc.compute.bar_capped`), so each has its cases below."""
    assert set(FAMILIES) == fused.T_BLOCKED_FAMILIES


def _grid(axes):
    return sweep.product_grid(
        **{k: jnp.asarray(v, jnp.float32) for k, v in axes.items()})


def _assert_close(got, ref, tol=TIGHT):
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            err_msg=name, **tol)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("T", [3 * BLOCK + 50, BLOCK - 28],
                         ids=["3-blocks-and-a-ragged-tail", "one-block"])
def test_t_blocked_cell_matches_generic(family, T):
    call, axes, tol = FAMILIES[family]
    ohlcv = data.synthetic_ohlcv(3, T, seed=T)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = _grid(axes)
    ref = sweep.jit_sweep(panel, get_strategy(family), dict(grid),
                          cost=1e-3)
    got = call(panel, grid, None, t_block=BLOCK)
    assert float(np.asarray(ref.turnover).max()) > 0   # some cells trade
    _assert_close(got, ref, tol)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_t_blocked_cell_ragged_rows(family):
    """Rows of 434, 300 and 129 real bars in one 4-block launch: each
    row's last real bar lies in another block, and its held position
    rides the carry through the blocks after it."""
    call, axes, tol = FAMILIES[family]
    series = []
    for i, T in enumerate([434, 300, 129]):
        one = data.synthetic_ohlcv(1, T, seed=80 + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    grid = _grid(axes)
    ref = sweep.jit_sweep(panel, get_strategy(family), dict(grid),
                          cost=1e-3, bar_mask=jnp.asarray(mask))
    _assert_close(call(panel, grid, lens, t_block=BLOCK), ref, tol)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_single_t_block_is_bit_identical_to_the_unblocked_cell(family):
    """The carry machinery over one block computes the program the
    one-block cell runs (the daily cells' program), to the bit."""
    call, axes, _ = FAMILIES[family]
    ohlcv = data.synthetic_ohlcv(2, 251, seed=5)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = _grid(axes)
    kw = {"table": "hbm"} if family.startswith("bollinger") else {}
    whole = call(panel, grid, None, **kw)
    blocked = call(panel, grid, None, t_block=256, **kw)
    for name in whole._fields:
        np.testing.assert_array_equal(np.asarray(getattr(blocked, name)),
                                      np.asarray(getattr(whole, name)),
                                      err_msg=name)


def test_band_t_pad_and_block():
    assert fused._band_t_pad(1260) == 1280
    assert fused._band_t_pad(8192) == 8192
    assert fused._t_block(8192) is None
    assert fused._band_t_pad(98280) == 98304
    assert fused._t_block(98304) == 2048
    assert fused._band_t_pad(434, 128) == 512


def test_t_blocked_launch_needs_a_dividing_block():
    close = jnp.ones((1, 300), jnp.float32)
    with pytest.raises(ValueError, match="T-blocked"):
        fused.fused_vwap_sweep(close, close, np.asarray([10.0]),
                               np.asarray([1.0]), t_block=100)


def _route(route):
    return obs.get_registry().counter("dbx_route_jobs_total",
                                      route=route).value


def _job(jid, strategy, ohlcv, axes):
    return pb.JobSpec(id=jid, strategy=strategy,
                      ohlcv=data.to_wire_bytes(ohlcv),
                      grid=wire.grid_to_proto(axes), cost=1e-3,
                      periods_per_year=98280)


def _minute_year(seed, bars=98280):
    """One ticker's year of minute bars: 30 % a year of volatility spread
    over the bars (``synthetic_ohlcv`` draws daily moves), lognormal
    volume."""
    rng = np.random.default_rng(seed)
    close = (60.0 * np.exp(np.cumsum(
        rng.normal(0.0, 0.3 / np.sqrt(bars), bars)))).astype(np.float32)
    volume = np.exp(rng.normal(14.0, 0.7, bars)).astype(np.float32)
    return data.OHLCV(close, close, close, close, volume)


def test_router_admits_a_minute_year_of_vwap_to_the_fused_route():
    bars = 98280
    assert not compute.bar_capped("vwap_reversion", bars, 8192)
    assert compute.bar_capped("sma_crossover", bars, 8192)
    series = _minute_year(11, bars)
    one = type(series)(*(f[None] for f in series))
    axes = {"k": np.float32([1.0, 2.0]), "window": np.float32([10.0, 390.0])}
    job = _job("minute", "vwap_reversion", series, axes)
    assert compute.JaxSweepBackend._fused_eligible(job, axes, [bars])
    backend = compute.JaxSweepBackend(use_fused=True)
    before = {r: _route(r) for r in ("fused", "generic")}
    (done,) = backend.process([job])
    assert _route("fused") - before["fused"] == 1
    assert _route("generic") == before["generic"]
    got = wire.metrics_from_bytes(done.metrics)
    panel = type(one)(*(jnp.asarray(f) for f in one))
    ref = sweep.jit_sweep(panel, get_strategy("vwap_reversion"),
                          dict(sweep.product_grid(**axes)), cost=1e-3,
                          periods_per_year=98280)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(ref, name))[0], rtol=2e-4, atol=2e-5,
            err_msg=name)


def test_router_still_demotes_a_long_family_without_t_blocks(caplog):
    bars = compute.JaxSweepBackend._FUSED_MAX_BARS + 808
    one = data.synthetic_ohlcv(1, bars, seed=12)
    axes = {"fast": np.float32([5.0]), "slow": np.float32([20.0])}
    job = _job("long-sma", "sma_crossover", type(one)(*(f[0] for f in one)),
               axes)
    assert not compute.JaxSweepBackend._fused_eligible(job, axes, [bars])
    backend = compute.JaxSweepBackend(use_fused=True)
    before = {r: _route(r) for r in ("fused", "generic")}
    backend.process([job])
    assert _route("generic") - before["generic"] == 1
    assert _route("fused") == before["fused"]
    assert any("demoted to the generic path" in r.message
               for r in caplog.records)


def test_page_pool_takes_a_share_of_the_device(monkeypatch):
    monkeypatch.delenv("DBX_PAGE_POOL_MB", raising=False)
    monkeypatch.setattr(page_pool, "device_memory_limit",
                        lambda: 16 * 2 ** 30)
    assert page_pool.pool_max_bytes() == 2 * 2 ** 30
    # A device that reports no limit (the CPU) keeps the default.
    monkeypatch.setattr(page_pool, "device_memory_limit", lambda: 0)
    assert page_pool.pool_max_bytes() == 64 * 2 ** 20
    # The variable stays an override.
    monkeypatch.setenv("DBX_PAGE_POOL_MB", "4")
    assert page_pool.pool_max_bytes() == 4 * 2 ** 20


def test_host_cache_holds_what_the_page_pool_holds(monkeypatch):
    monkeypatch.delenv("DBX_PANEL_CACHE_MB", raising=False)
    monkeypatch.delenv("DBX_PAGE_POOL_MB", raising=False)
    monkeypatch.setattr(page_pool, "device_memory_limit",
                        lambda: 16 * 2 ** 30)
    cache = compute.PanelCache(registry=obs.Registry())
    assert cache.host_max_bytes == 5 * 2 * 2 ** 30
    assert cache.max_bytes == 256 * 2 ** 20          # the device level
    monkeypatch.setenv("DBX_PANEL_CACHE_MB", "32")
    cache = compute.PanelCache(registry=obs.Registry())
    assert cache.host_max_bytes == cache.max_bytes == 32 * 2 ** 20
