"""AOT compiles of the main-path kernels for a described v5e (no chip).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what it refuses (VMEM over the scoped limit, a
misaligned block) it refuses here at no chip time. Each test compiles one
fused kernel at a real width with ``interpret=False`` and asserts the
Mosaic kernel is in the program (``tpu_custom_call``).

The topology is described only inside the module fixture — never at
import, in a ``skipif`` or in ``parametrize`` — because only one process
may hold the TPU library: under pytest-xdist every worker imports this
file, and only the worker that runs these tests may load it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_backtesting_exploration_tpu.ops import fused
from distributed_backtesting_exploration_tpu.parallel import sweep
from distributed_backtesting_exploration_tpu.rpc.compute import (
    JaxSweepBackend)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-device compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off around
    # this module.
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prior)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _np(grid):
    return {k: np.asarray(v) for k, v in grid.items()}


def test_sma_north_star(one_chip):
    """500 tickers x 1,260 bars x 2,000 combos, inline table."""
    g = _np(sweep.product_grid(fast=np.arange(5, 25, dtype=np.float32),
                               slow=np.arange(30, 230, 2,
                                              dtype=np.float32)))
    _compile(lambda c: fused.fused_sma_sweep(
        c, g["fast"], g["slow"], cost=1e-3, interpret=False,
        table="inline"), (500, 1260), sharding=one_chip)


def test_bollinger_band_machine(one_chip):
    """500 x 1,260 x 1,000 combos through the 3-state band machine."""
    g = _np(sweep.product_grid(
        window=np.arange(10, 50, 2, dtype=np.float32),
        k=np.linspace(0.5, 3.0, 50).astype(np.float32)))
    _compile(lambda c: fused.fused_bollinger_sweep(
        c, g["window"], g["k"], cost=1e-3, interpret=False),
        (500, 1260), sharding=one_chip)


def test_pairs(one_chip):
    """250 pairs x 1,260 bars x 400 combos."""
    g = _np(sweep.product_grid(
        lookback=np.arange(10, 50, 2, dtype=np.float32),
        z_entry=np.linspace(0.5, 3.0, 20).astype(np.float32)))
    _compile(lambda y, x: fused.fused_pairs_sweep(
        y, x, g["lookback"], g["z_entry"], cost=1e-3, interpret=False),
        (250, 1260), (250, 1260), sharding=one_chip)


def test_paged_ragged_sma_group(one_chip):
    """The default one-chip route: a ragged group gathered from the page
    pool (one 3-page bin, per-ticker lengths in SMEM)."""
    page = 512
    t_real = np.array([1260, 1100, 1200, 1030] * 16, np.int32)
    n = t_real.shape[0]
    pages = -(-int(t_real.max()) // page)
    tables = {"close": np.arange(n * pages, dtype=np.int32).reshape(n,
                                                                    pages)}
    g = _np(sweep.product_grid(fast=np.arange(5, 25, dtype=np.float32),
                               slow=np.arange(30, 230, 2,
                                              dtype=np.float32)))
    _compile(lambda pool: fused.fused_paged_sweep(
        "sma_crossover", pool, tables, t_real, g, cost=1e-3,
        interpret=False), (n * pages, page), sharding=one_chip)


def test_router_bar_cap(one_chip):
    """The longest history and the most table windows the router sends to
    a fused kernel, for the kernel with the widest VMEM working set (the
    SMA sign kernel: 512 lanes plus its in-VMEM window table)."""
    bars = JaxSweepBackend._FUSED_MAX_BARS
    half = JaxSweepBackend._FUSED_MAX_WINDOWS // 2
    wins = np.arange(2 * half, dtype=np.float32) * 2 + 2
    g = _np(sweep.product_grid(fast=wins[:half], slow=wins[half:]))
    _compile(lambda c: fused.fused_sma_sweep(
        c, g["fast"], g["slow"], cost=1e-3, interpret=False),
        (8, bars), sharding=one_chip)


# A year of minute bars (390 x 252) on the sp500_minute_vwap cell's grid:
# 39 windows x 32 bands, through the T-blocked band machine. The other
# band families take the same shape, on their own axis names.
MINUTE_BARS = 98280
MINUTE_WINDOWS = np.arange(10, 400, 10, dtype=np.float32)
MINUTE_AXES = {"window": MINUTE_WINDOWS,
               "k": np.arange(0.5, 4.5, 0.125, dtype=np.float32)}
MINUTE_BANDS = np.arange(5.0, 37.0, dtype=np.float32)

# strategy -> (window axis, band axis, OHLCV fields the kernel reads,
# the fused call over those fields and the grid ``g``).
BAND_FAMILIES = {
    "pairs": ("lookback", "z_entry", 2, lambda g, y, x:
              fused.fused_pairs_sweep(y, x, g["lookback"], g["z_entry"],
                                      cost=1e-3, interpret=False)),
    "vwap_reversion": ("window", "k", 2, lambda g, c, v:
                       fused.fused_vwap_sweep(c, v, g["window"], g["k"],
                                              cost=1e-3, interpret=False)),
    "bollinger": ("window", "k", 1, lambda g, c:
                  fused.fused_bollinger_sweep(c, g["window"], g["k"],
                                              cost=1e-3, interpret=False)),
    "bollinger_touch": ("window", "k", 1, lambda g, c:
                        fused.fused_bollinger_touch_sweep(
                            c, g["window"], g["k"], cost=1e-3,
                            interpret=False)),
    "rsi": ("period", "band", 1, lambda g, c:
            fused.fused_rsi_sweep(c, g["period"], g["band"], cost=1e-3,
                                  interpret=False)),
    "stochastic": ("window", "band", 3, lambda g, c, h, lo:
                   fused.fused_stochastic_sweep(c, h, lo, g["window"],
                                                g["band"], cost=1e-3,
                                                interpret=False)),
    "keltner": ("window", "k", 3, lambda g, c, h, lo:
                fused.fused_keltner_sweep(c, h, lo, g["window"], g["k"],
                                          cost=1e-3, interpret=False)),
}


@pytest.mark.parametrize("strategy,bars", [
    pytest.param("pairs", None, id="pairs"),
    pytest.param("vwap_reversion", None, id="vwap_reversion"),
    pytest.param("vwap_reversion", MINUTE_BARS, id="vwap_reversion-minute"),
    pytest.param("bollinger", MINUTE_BARS, id="bollinger-minute"),
    pytest.param("bollinger_touch", MINUTE_BARS,
                 id="bollinger_touch-minute"),
    pytest.param("rsi", MINUTE_BARS, id="rsi-minute"),
    pytest.param("stochastic", MINUTE_BARS, id="stochastic-minute"),
    pytest.param("keltner", MINUTE_BARS, id="keltner-minute"),
])
def test_router_group_at_the_bar_cap_fits_hbm(one_chip, strategy, bars):
    """The largest group the router forms at its bar cap, for the two
    families that take the most device memory there (their 3-D z-table
    preps: pairs ~2.8 KB per ticker and bar at 128 windows, VWAP ~2.3
    KB): the compiled program fits the HBM budget the router sizes
    groups by. The minute cases are a year of minute bars, past the cap,
    on the sp500_minute_vwap grid's shape (39 windows x 32 bands), for
    every family the router takes past it (``ops.fused.
    T_BLOCKED_FAMILIES``): the T-blocked kernel compiles there, and the
    router's group fits."""
    from distributed_backtesting_exploration_tpu.rpc import (
        backtesting_pb2 as pb, wire)

    window, k, fields, call = BAND_FAMILIES[strategy]
    if bars is None:
        bars = JaxSweepBackend._FUSED_MAX_BARS
        axes = {window: np.arange(JaxSweepBackend._FUSED_MAX_WINDOWS,
                                  dtype=np.float32) * 2 + 10,
                k: np.linspace(0.5, 3.0, 8).astype(np.float32)}
    else:
        axes = {window: MINUTE_WINDOWS,
                k: MINUTE_AXES["k"] if k == "k" else MINUTE_BANDS}
    legs = 2 if strategy == "pairs" else 0
    job = pb.JobSpec(strategy=strategy, panel_bytes_len=8 + 20 * bars,
                     panel_bytes_len2=legs and 8 + 20 * bars,
                     grid=wire.grid_to_proto(axes))
    n = JaxSweepBackend(use_fused=False, use_mesh=False)._group_limit([job])
    g = _np(sweep.product_grid(**axes))
    args = [jax.ShapeDtypeStruct((n, bars), jnp.float32, sharding=one_chip)
            for _ in range(fields)]
    compiled = jax.jit(lambda *a: call(g, *a)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used <= JaxSweepBackend._HBM_BUDGET, (n, used)


def test_minute_families_are_the_t_blocked_ones():
    assert set(BAND_FAMILIES) - {"pairs"} == fused.T_BLOCKED_FAMILIES
