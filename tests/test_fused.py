"""Fused Pallas sweep kernel vs the generic sweep path (golden equality).

On CPU the kernel runs in interpret mode and must match the generic
jit+vmap path to float32 tolerance for every metric, including the
unaligned-T padding path and non-square grids.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_backtesting_exploration_tpu.models.base import get_strategy
from distributed_backtesting_exploration_tpu.ops import fused
from distributed_backtesting_exploration_tpu.parallel import sweep
from distributed_backtesting_exploration_tpu.utils import data, verify


def _check(n_tickers, T, fast_axis, slow_axis, cost=1e-3, seed=0):
    ohlcv = data.synthetic_ohlcv(n_tickers, T, seed=seed)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(fast=jnp.asarray(fast_axis, jnp.float32),
                              slow=jnp.asarray(slow_axis, jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("sma_crossover"), dict(grid),
                          cost=cost)
    got = fused.fused_sma_sweep(
        panel.close, np.asarray(grid["fast"]), np.asarray(grid["slow"]),
        cost=cost)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_matches_generic_small():
    _check(3, 200, [3, 5, 8], [13, 21])


def test_fused_matches_generic_unaligned_T():
    # T=251 pads to 256: padded bars must not alter any metric.
    _check(2, 251, [4, 6], [17, 29], seed=3)


def test_fused_matches_generic_wide_grid():
    # More params than one 128-lane block; shared windows across combos.
    _check(2, 320, list(range(3, 14)), list(range(20, 44, 2)), seed=5)


def test_fused_single_param():
    _check(1, 137, [5], [20], seed=7)


def test_fused_zero_cost():
    _check(2, 200, [3, 7], [15, 31], cost=0.0, seed=9)


def test_fused_rejects_non_integer_windows():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_sma_sweep(
            jnp.ones((1, 64)), np.asarray([3.5]), np.asarray([10.0]))


def test_fused_inline_table_matches_hbm_table():
    # The in-kernel (VMEM-scratch) table build must be BIT-identical to the
    # XLA-built HBM table path — same op sequence per row, wrapped rotate
    # lanes zeroed like _shift_t's fill (ops/fused.py `_kernel_inline`).
    ohlcv = data.synthetic_ohlcv(3, 300, seed=11)
    close = jnp.asarray(ohlcv.close)
    grid = sweep.product_grid(fast=jnp.asarray([3, 5, 8], jnp.float32),
                              slow=jnp.asarray([13, 21, 34], jnp.float32))
    fa, sl = np.asarray(grid["fast"]), np.asarray(grid["slow"])
    a = fused.fused_sma_sweep(close, fa, sl, cost=1e-3, table="hbm")
    b = fused.fused_sma_sweep(close, fa, sl, cost=1e-3, table="inline")
    for name in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


def test_fused_inline_table_multi_block_scratch_persists():
    # 25x25 = 625 combos -> P_pad 640 -> lanes 128 -> n_blocks = 5: the
    # VMEM-scratch table is built at param-block j == 0 only and must
    # still be live (and correct) for j = 1..4. A stale/garbage scratch
    # would corrupt every combo beyond the first 128 lanes while the
    # single-block tests stay green.
    ohlcv = data.synthetic_ohlcv(2, 220, seed=13)
    close = jnp.asarray(ohlcv.close)
    grid = sweep.product_grid(
        fast=jnp.arange(3, 28, dtype=jnp.float32),
        slow=jnp.arange(30, 80, 2, dtype=jnp.float32))
    fa, sl = np.asarray(grid["fast"]), np.asarray(grid["slow"])
    assert fa.size == 625
    a = fused.fused_sma_sweep(close, fa, sl, cost=1e-3, table="hbm")
    b = fused.fused_sma_sweep(close, fa, sl, cost=1e-3, table="inline")
    for name in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


def test_fused_inline_table_matches_hbm_table_ragged():
    ohlcv = data.synthetic_ohlcv(3, 300, seed=12)
    close = jnp.asarray(ohlcv.close)
    t_real = np.asarray([300, 251, 170], np.int32)
    fa = np.asarray([4.0, 6.0], np.float32)
    sl = np.asarray([17.0, 29.0], np.float32)
    a = fused.fused_sma_sweep(close, fa, sl, t_real=t_real, cost=1e-3,
                              table="hbm")
    b = fused.fused_sma_sweep(close, fa, sl, t_real=t_real, cost=1e-3,
                              table="inline")
    for name in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


def test_fused_rejects_unknown_table_mode():
    with pytest.raises(ValueError, match="table"):
        fused.fused_sma_sweep(
            jnp.ones((1, 64)), np.asarray([3.0]), np.asarray([10.0]),
            table="nope")


def test_momentum_donchian_inline_tables_match_hbm():
    # The momentum past-close and Donchian breakout-sign in-kernel tables
    # involve no arithmetic (rotate / max / compare of raw prices), so
    # unlike the SMA inline table they must be bit-identical to the
    # XLA-table substrate on EVERY backend. 300 params -> P_pad 384 ->
    # 128-lane blocks x 3 for every cap, so the scratch-persistence
    # window (blocks j > 0 reading the table built at j == 0) is
    # exercised for all three inline kernels.
    ohlcv = data.synthetic_ohlcv(3, 300, seed=21)
    close = jnp.asarray(ohlcv.close)
    high = jnp.asarray(ohlcv.high)
    low = jnp.asarray(ohlcv.low)
    lb = np.linspace(4, 90, 300).round().astype(np.float32)
    assert lb.size == 300 and -(-lb.size // 128) * 128 == 384
    cases = [
        ("momentum", lambda m: fused.fused_momentum_sweep(
            close, lb, cost=1e-3, table=m)),
        ("donchian", lambda m: fused.fused_donchian_sweep(
            close, lb, cost=1e-3, table=m)),
        ("donchian_hl", lambda m: fused.fused_donchian_hl_sweep(
            close, high, low, lb, cost=1e-3, table=m)),
    ]
    for name, mk in cases:
        a, b = mk("hbm"), mk("inline")
        for field in a._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=f"{name}.{field}")


def test_obv_inline_table_matches_hbm():
    # SMA-of-OBV table built in VMEM scratch (`_obv_kernel_inline`) vs the
    # W-major XLA table: bit-identical on CPU (the on-TPU 1-ULP division
    # caveat is the SMA inline substrate's, gated by bench --verify).
    # 300 params -> P_pad 384 -> 3 blocks: covers scratch persistence.
    ohlcv = data.synthetic_ohlcv(3, 300, seed=23)
    w = np.linspace(5, 90, 300).round().astype(np.float32)
    a = fused.fused_obv_sweep(ohlcv.close, ohlcv.volume, w, cost=1e-3,
                              table="hbm")
    b = fused.fused_obv_sweep(ohlcv.close, ohlcv.volume, w, cost=1e-3,
                              table="inline")
    for field in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=field)


def test_bollinger_inline_ztable_matches_hbm():
    # The in-kernel z-table build (`_band_kernel_inline` /
    # `_build_boll_z_scratch`) vs the XLA-built z-table, both machines:
    # bit-identical on CPU (the on-TPU 1-ULP div/sqrt caveat is gated by
    # bench --verify). window axis deliberately sized so W_pad (8-row
    # sublane padding) EXCEEDS the distinct-window count — the scratch pad
    # rows must be zeroed, not left as garbage VMEM (a NaN there survives
    # the 0-weight one-hot contraction and silently flattens positions).
    ohlcv = data.synthetic_ohlcv(3, 300, seed=29)
    close = jnp.asarray(ohlcv.close)
    grid = sweep.product_grid(
        window=jnp.asarray([10, 17, 26], jnp.float32),
        k=jnp.asarray([0.8, 1.5, 2.2], jnp.float32))
    w, k = np.asarray(grid["window"]), np.asarray(grid["k"])
    cases = [
        ("bollinger", lambda m: fused.fused_bollinger_sweep(
            close, w, k, cost=1e-3, table=m)),
        ("bollinger_touch", lambda m: fused.fused_bollinger_touch_sweep(
            close, w, k, cost=1e-3, table=m)),
    ]
    for name, mk in cases:
        a, b = mk("hbm"), mk("inline")
        for field in a._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=f"{name}.{field}")


def test_bollinger_inline_ztable_multi_block_ragged():
    # 25 windows x 24 k = 600 combos -> P_pad 640 -> 128-lane blocks x 5:
    # scratch persistence across param blocks, plus per-ticker lengths.
    ohlcv = data.synthetic_ohlcv(3, 300, seed=31)
    close = jnp.asarray(ohlcv.close)
    t_real = np.asarray([300, 254, 147], np.int32)
    grid = sweep.product_grid(
        window=jnp.arange(10, 60, 2, dtype=jnp.float32),
        k=jnp.linspace(0.5, 3.0, 24).astype(jnp.float32))
    w, k = np.asarray(grid["window"]), np.asarray(grid["k"])
    for machine, fn in (("bollinger", fused.fused_bollinger_sweep),
                        ("bollinger_touch",
                         fused.fused_bollinger_touch_sweep)):
        a = fn(close, w, k, t_real=t_real, cost=1e-3, table="hbm")
        b = fn(close, w, k, t_real=t_real, cost=1e-3, table="inline")
        for field in a._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=f"{machine}.{field}")


def test_momentum_inline_table_ragged_matches_hbm():
    ohlcv = data.synthetic_ohlcv(3, 300, seed=22)
    close = jnp.asarray(ohlcv.close)
    t_real = np.asarray([300, 240, 130], np.int32)
    lb = np.asarray([5.0, 20.0, 63.0], np.float32)
    a = fused.fused_momentum_sweep(close, lb, t_real=t_real, cost=1e-3,
                                   table="hbm")
    b = fused.fused_momentum_sweep(close, lb, t_real=t_real, cost=1e-3,
                                   table="inline")
    for field in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=field)


def _check_boll(n_tickers, T, window_axis, k_axis, cost=1e-3, seed=0,
                z_exit=0.0):
    ohlcv = data.synthetic_ohlcv(n_tickers, T, seed=seed)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(k=jnp.asarray(k_axis, jnp.float32),
                              window=jnp.asarray(window_axis, jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("bollinger"), dict(grid),
                          cost=cost)
    got = fused.fused_bollinger_sweep(
        panel.close, np.asarray(grid["window"]), np.asarray(grid["k"]),
        cost=cost, z_exit=z_exit)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_bollinger_matches_generic_small():
    _check_boll(3, 200, [10, 20, 30], [0.5, 1.0, 2.0])


def test_fused_bollinger_unaligned_T():
    _check_boll(2, 251, [8, 16], [1.0, 1.5], seed=3)


def test_fused_bollinger_wide_grid():
    # More params than one 128-lane block; shared windows across combos.
    _check_boll(2, 320, list(range(5, 16)), [0.5, 1.0, 1.5, 2.0, 2.5, 3.0],
                seed=5)


def test_fused_bollinger_single_param():
    _check_boll(1, 137, [12], [1.5], seed=7)


def test_fused_bollinger_zero_cost():
    _check_boll(2, 200, [10, 25], [1.0, 2.0], cost=0.0, seed=9)


def test_fused_bollinger_rejects_non_integer_windows():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_bollinger_sweep(
            jnp.ones((1, 64)), np.asarray([10.5]), np.asarray([1.0]))


def _check_ragged(strategy, fused_fn, axes, lengths, cost=1e-3, seed=0):
    """Fused with per-ticker t_real vs the generic ragged path
    (pad_and_stack + bar_mask)."""
    series = []
    for i, T in enumerate(lengths):
        one = data.synthetic_ohlcv(1, T, seed=seed + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    grid = sweep.product_grid(**axes)
    ref = sweep.jit_sweep(panel, get_strategy(strategy), dict(grid),
                          cost=cost, bar_mask=jnp.asarray(mask))
    got = fused_fn(batch.close, grid, lens)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_sma_ragged_lengths():
    _check_ragged(
        "sma_crossover",
        lambda close, g, lens: fused.fused_sma_sweep(
            close, np.asarray(g["fast"]), np.asarray(g["slow"]),
            t_real=lens, cost=1e-3),
        dict(fast=jnp.asarray([3, 5, 8], jnp.float32),
             slow=jnp.asarray([13, 21], jnp.float32)),
        lengths=[150, 200, 97, 200])


def test_fused_bollinger_ragged_lengths():
    _check_ragged(
        "bollinger",
        lambda close, g, lens: fused.fused_bollinger_sweep(
            close, np.asarray(g["window"]), np.asarray(g["k"]),
            t_real=lens, cost=1e-3),
        dict(window=jnp.asarray([10, 20], jnp.float32),
             k=jnp.asarray([1.0, 2.0], jnp.float32)),
        lengths=[180, 131, 256], seed=11)


def test_fused_uniform_t_real_matches_default():
    # An explicit full-length t_real routes through the dynamic-length
    # kernel; it must agree with the static fast path to float noise
    # (ulp-level fusion differences only).
    ohlcv = data.synthetic_ohlcv(2, 100, seed=3)
    close = jnp.asarray(ohlcv.close)
    fa, sl = np.asarray([3.0, 5.0]), np.asarray([11.0, 17.0])
    a = fused.fused_sma_sweep(close, fa, sl, cost=1e-3)
    b = fused.fused_sma_sweep(close, fa, sl, t_real=np.asarray([100, 100]),
                              cost=1e-3)
    for name in a._fields:
        np.testing.assert_allclose(np.asarray(getattr(a, name)),
                                   np.asarray(getattr(b, name)),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_padding_invariance_property():
    """Appending pad bars (repeat-last close + t_real) must not change any
    metric — the padding-discipline invariant every kernel's correctness
    rests on, asserted directly rather than only via generic-path parity."""
    ohlcv = data.synthetic_ohlcv(3, 150, seed=13)
    close = np.asarray(ohlcv.close)
    padded = np.concatenate(
        [close, np.repeat(close[:, -1:], 37, axis=1)], axis=1)
    t_real = np.full(3, 150, np.int32)

    fa, sl = np.asarray([4.0, 7.0]), np.asarray([15.0, 25.0])
    a = fused.fused_sma_sweep(close, fa, sl, t_real=t_real, cost=1e-3)
    b = fused.fused_sma_sweep(padded, fa, sl, t_real=t_real, cost=1e-3)
    for name in a._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            rtol=1e-5, atol=1e-6, err_msg=f"sma/{name}")

    w, k = np.asarray([10.0, 20.0]), np.asarray([1.0, 2.0])
    a = fused.fused_bollinger_sweep(close, w, k, t_real=t_real, cost=1e-3)
    b = fused.fused_bollinger_sweep(padded, w, k, t_real=t_real, cost=1e-3)
    for name in a._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            rtol=1e-5, atol=1e-6, err_msg=f"boll/{name}")


def _check_pairs(n_pairs, T, lookback_axis, z_entry_axis, cost=1e-3, seed=0,
                 z_exit=None):
    from distributed_backtesting_exploration_tpu.models import pairs

    ohlcv = data.synthetic_ohlcv(2 * n_pairs, T, seed=seed)
    closes = jnp.asarray(ohlcv.close)
    y_close, x_close = closes[:n_pairs], closes[n_pairs:]
    axes = dict(lookback=jnp.asarray(lookback_axis, jnp.float32),
                z_entry=jnp.asarray(z_entry_axis, jnp.float32))
    if z_exit is not None:
        axes["z_exit"] = jnp.asarray(z_exit, jnp.float32)
    grid = sweep.product_grid(**axes)
    ref = pairs.run_pairs_sweep(y_close, x_close, dict(grid), cost=cost)
    got = fused.fused_pairs_sweep(
        y_close, x_close, np.asarray(grid["lookback"]),
        np.asarray(grid["z_entry"]),
        z_exit=np.asarray(grid["z_exit"]) if z_exit is not None else 0.0,
        cost=cost)
    # The fused prep computes windowed sums as banded-matrix tree sums (MXU);
    # the generic path differences a cumsum. Both are valid f32 evaluations,
    # so z-scores differ by ~1e-6 — which (a) loosens per-metric tolerances
    # vs the single-asset kernels and (b) can flip a knife-edge band entry,
    # diverging that cell's whole position path. Flips must stay rare
    # (<= 1% of cells); non-flipped cells must match tightly.
    # A flipped cell shows a *gross* mismatch in at least one metric (a
    # diverged path can coincidentally preserve, say, total turnover, so no
    # single field is a reliable detector — union them).
    flipped = np.zeros_like(np.asarray(got.turnover), dtype=bool)
    for name in ref._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(ref, name))
        flipped |= np.abs(a - b) > (0.01 + 0.01 * np.abs(b))
    n_flips = int(flipped.sum())
    assert n_flips <= max(1, int(0.01 * flipped.size)), (
        f"{n_flips}/{flipped.size} position-path flips")
    for name in ref._fields:
        a = np.asarray(getattr(got, name))[~flipped]
        b = np.asarray(getattr(ref, name))[~flipped]
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)


def test_fused_pairs_matches_generic_small():
    _check_pairs(3, 200, [10, 20, 30], [0.5, 1.0, 2.0])


def test_fused_pairs_unaligned_T():
    # T=251 pads to 256: padded bars must not alter any metric.
    _check_pairs(2, 251, [8, 16], [1.0, 1.5], seed=3)


def test_fused_pairs_wide_grid():
    # More params than one 128-lane block; shared lookbacks across combos.
    _check_pairs(2, 320, list(range(5, 16)),
                 [0.5, 1.0, 1.5, 2.0, 2.5, 3.0, 0.8, 1.2, 1.8, 2.2, 2.8, 0.6],
                 seed=5)


def test_fused_pairs_single_param():
    _check_pairs(1, 137, [12], [1.5], seed=7)


def test_fused_pairs_zero_cost():
    _check_pairs(2, 200, [10, 25], [1.0, 2.0], cost=0.0, seed=9)


def test_fused_pairs_per_lane_z_exit():
    # z_exit in the grid: each lane carries its own exit band.
    _check_pairs(2, 200, [10, 20], [1.0, 2.0], z_exit=[0.0, 0.5], seed=11)


def test_fused_pairs_rejects_non_integer_lookbacks():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_pairs_sweep(
            jnp.ones((1, 64)), jnp.ones((1, 64)),
            np.asarray([10.5]), np.asarray([1.0]))


def _check_single_axis(strategy, fused_fn, axis_name, axis_vals, n_tickers=3,
                       T=200, cost=1e-3, seed=0):
    ohlcv = data.synthetic_ohlcv(n_tickers, T, seed=seed)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(
        **{axis_name: jnp.asarray(axis_vals, jnp.float32)})
    ref = sweep.jit_sweep(panel, get_strategy(strategy), dict(grid),
                          cost=cost)
    got = fused_fn(panel.close, np.asarray(grid[axis_name]), cost=cost)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_momentum_matches_generic():
    _check_single_axis("momentum", fused.fused_momentum_sweep, "lookback",
                       [5, 10, 21, 63])


def test_fused_momentum_unaligned_T():
    _check_single_axis("momentum", fused.fused_momentum_sweep, "lookback",
                       [8, 13], T=251, seed=3)


def test_fused_donchian_matches_generic():
    _check_single_axis("donchian", fused.fused_donchian_sweep, "window",
                       [10, 20, 55], seed=5)


def test_fused_donchian_unaligned_T():
    _check_single_axis("donchian", fused.fused_donchian_sweep, "window",
                       [15, 30], T=251, seed=7)


def test_fused_momentum_donchian_ragged():
    series = []
    for i, T in enumerate([150, 200, 97]):
        one = data.synthetic_ohlcv(1, T, seed=20 + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    for strategy, fused_fn, axis in (
            ("momentum", fused.fused_momentum_sweep, "lookback"),
            ("donchian", fused.fused_donchian_sweep, "window")):
        grid = sweep.product_grid(
            **{axis: jnp.asarray([10.0, 20.0], jnp.float32)})
        ref = sweep.jit_sweep(panel, get_strategy(strategy), dict(grid),
                              cost=1e-3, bar_mask=jnp.asarray(mask))
        got = fused_fn(batch.close, np.asarray(grid[axis]), t_real=lens,
                       cost=1e-3)
        for name in ref._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(ref, name)),
                rtol=2e-4, atol=2e-5, err_msg=f"{strategy}/{name}")


def test_fused_momentum_rejects_non_integer_lookbacks():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_momentum_sweep(jnp.ones((1, 64)), np.asarray([10.5]))


def test_fused_rsi_matches_generic():
    ohlcv = data.synthetic_ohlcv(3, 200, seed=17)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(
        period=jnp.asarray([7.0, 14.0, 21.0], jnp.float32),
        band=jnp.asarray([15.0, 20.0, 25.0], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("rsi"), dict(grid), cost=1e-3)
    got = fused.fused_rsi_sweep(panel.close, np.asarray(grid["period"]),
                                np.asarray(grid["band"]), cost=1e-3)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_rsi_ragged():
    series = []
    for i, T in enumerate([150, 200, 97]):
        one = data.synthetic_ohlcv(1, T, seed=30 + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    grid = sweep.product_grid(period=jnp.asarray([10.0, 14.0], jnp.float32),
                              band=jnp.asarray([20.0], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("rsi"), dict(grid), cost=1e-3,
                          bar_mask=jnp.asarray(mask))
    got = fused.fused_rsi_sweep(batch.close, np.asarray(grid["period"]),
                                np.asarray(grid["band"]), t_real=lens,
                                cost=1e-3)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def _macd_flip_aware_check(got, ref):
    # The in-kernel signal-EMA ladder rounds differently from XLA's
    # associative_scan, so a knife-edge macd/signal crossing can resolve
    # differently and diverge that cell's path; require such flips rare and
    # everything else tight (same discipline as the pairs kernel).
    flipped = np.zeros_like(np.asarray(got.turnover), dtype=bool)
    for name in ref._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(ref, name))
        flipped |= np.abs(a - b) > (0.01 + 0.01 * np.abs(b))
    assert int(flipped.sum()) <= max(1, int(0.01 * flipped.size)), (
        f"{int(flipped.sum())}/{flipped.size} flips")
    for name in ref._fields:
        a = np.asarray(getattr(got, name))[~flipped]
        b = np.asarray(getattr(ref, name))[~flipped]
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4, err_msg=name)


def test_fused_macd_matches_generic():
    ohlcv = data.synthetic_ohlcv(3, 200, seed=19)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(
        fast=jnp.asarray([8.0, 12.0], jnp.float32),
        slow=jnp.asarray([26.0, 35.0], jnp.float32),
        signal=jnp.asarray([9.0, 5.0], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("macd"), dict(grid), cost=1e-3)
    got = fused.fused_macd_sweep(
        panel.close, np.asarray(grid["fast"]), np.asarray(grid["slow"]),
        np.asarray(grid["signal"]), cost=1e-3)
    _macd_flip_aware_check(got, ref)


def test_fused_macd_ragged():
    series = []
    for i, T in enumerate([150, 200, 97]):
        one = data.synthetic_ohlcv(1, T, seed=40 + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    grid = sweep.product_grid(
        fast=jnp.asarray([8.0, 12.0], jnp.float32),
        slow=jnp.asarray([26.0], jnp.float32),
        signal=jnp.asarray([9.0], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("macd"), dict(grid), cost=1e-3,
                          bar_mask=jnp.asarray(mask))
    got = fused.fused_macd_sweep(
        batch.close, np.asarray(grid["fast"]), np.asarray(grid["slow"]),
        np.asarray(grid["signal"]), t_real=lens, cost=1e-3)
    _macd_flip_aware_check(got, ref)


def test_fused_trix_matches_generic():
    ohlcv = data.synthetic_ohlcv(3, 200, seed=23)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(
        span=jnp.asarray([5.0, 9.0, 15.0], jnp.float32),
        signal=jnp.asarray([4.0, 9.0], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("trix"), dict(grid), cost=1e-3)
    got = fused.fused_trix_sweep(
        panel.close, np.asarray(grid["span"]), np.asarray(grid["signal"]),
        cost=1e-3)
    _macd_flip_aware_check(got, ref)


def test_fused_trix_ragged():
    series = []
    for i, T in enumerate([150, 200, 97]):
        one = data.synthetic_ohlcv(1, T, seed=60 + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    grid = sweep.product_grid(
        span=jnp.asarray([5.0, 9.0], jnp.float32),
        signal=jnp.asarray([4.0], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("trix"), dict(grid), cost=1e-3,
                          bar_mask=jnp.asarray(mask))
    got = fused.fused_trix_sweep(
        batch.close, np.asarray(grid["span"]), np.asarray(grid["signal"]),
        t_real=lens, cost=1e-3)
    _macd_flip_aware_check(got, ref)


def test_fused_trix_rejects_non_integer_spans():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_trix_sweep(
            jnp.ones((1, 64)), np.asarray([10.5]), np.asarray([4.0]))


def _check_panel_sweep(strategy, fused_call, grid_axes, n_tickers=3, T=200,
                       cost=1e-3, seed=0, rtol=2e-4, atol=2e-5):
    """Generic-vs-fused parity for strategies consuming non-close columns:
    the fused callable receives the full panel + materialized grid."""
    ohlcv = data.synthetic_ohlcv(n_tickers, T, seed=seed)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(**grid_axes)
    ref = sweep.jit_sweep(panel, get_strategy(strategy), dict(grid),
                          cost=cost)
    got = fused_call(panel, grid, None)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=rtol, atol=atol, err_msg=name)


def _check_panel_ragged(strategy, fused_call, grid_axes, lengths, cost=1e-3,
                        seed=0):
    series = []
    for i, T in enumerate(lengths):
        one = data.synthetic_ohlcv(1, T, seed=seed + i)
        series.append(type(one)(*(f[0] for f in one)))
    batch, lens, mask = data.pad_and_stack(series)
    panel = type(batch)(*(jnp.asarray(f) for f in batch))
    grid = sweep.product_grid(**grid_axes)
    ref = sweep.jit_sweep(panel, get_strategy(strategy), dict(grid),
                          cost=cost, bar_mask=jnp.asarray(mask))
    got = fused_call(panel, grid, lens)
    for name in ref._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(ref, name)),
            rtol=2e-4, atol=2e-5, err_msg=name)


def _don_hl_call(panel, grid, lens):
    return fused.fused_donchian_hl_sweep(
        panel.close, panel.high, panel.low, np.asarray(grid["window"]),
        t_real=lens, cost=1e-3)


def _vwap_call(panel, grid, lens):
    return fused.fused_vwap_sweep(
        panel.close, panel.volume, np.asarray(grid["window"]),
        np.asarray(grid["k"]), t_real=lens, cost=1e-3)


def test_fused_donchian_hl_matches_generic():
    _check_panel_sweep(
        "donchian_hl", _don_hl_call,
        dict(window=jnp.asarray([10, 20, 55], jnp.float32)), seed=5)


def test_fused_donchian_hl_unaligned_T():
    _check_panel_sweep(
        "donchian_hl", _don_hl_call,
        dict(window=jnp.asarray([15, 30], jnp.float32)), T=251, seed=7)


def test_fused_donchian_hl_ragged():
    _check_panel_ragged(
        "donchian_hl", _don_hl_call,
        dict(window=jnp.asarray([10.0, 20.0], jnp.float32)),
        lengths=[150, 200, 97], seed=50)


def test_fused_vwap_matches_generic():
    _check_panel_sweep(
        "vwap_reversion", _vwap_call,
        dict(window=jnp.asarray([10, 20, 30], jnp.float32),
             k=jnp.asarray([0.5, 1.0, 2.0], jnp.float32)), seed=13)


def test_fused_vwap_unaligned_T():
    _check_panel_sweep(
        "vwap_reversion", _vwap_call,
        dict(window=jnp.asarray([8, 16], jnp.float32),
             k=jnp.asarray([1.0, 1.5], jnp.float32)), T=251, seed=15)


def test_fused_vwap_ragged():
    _check_panel_ragged(
        "vwap_reversion", _vwap_call,
        dict(window=jnp.asarray([10.0, 20.0], jnp.float32),
             k=jnp.asarray([1.0, 2.0], jnp.float32)),
        lengths=[180, 131, 256], seed=60)


def _obv_call(panel, grid, lens):
    return fused.fused_obv_sweep(
        panel.close, panel.volume, np.asarray(grid["window"]),
        t_real=lens, cost=1e-3)


def test_fused_obv_matches_generic():
    _check_panel_sweep(
        "obv_trend", _obv_call,
        dict(window=jnp.asarray([8, 15, 30], jnp.float32)), seed=17)


def test_fused_obv_unaligned_T():
    _check_panel_sweep(
        "obv_trend", _obv_call,
        dict(window=jnp.asarray([10, 21], jnp.float32)), T=251, seed=19)


def test_fused_obv_ragged():
    _check_panel_ragged(
        "obv_trend", _obv_call,
        dict(window=jnp.asarray([8.0, 20.0], jnp.float32)),
        lengths=[180, 131, 256], seed=70)


def test_fused_obv_rejects_non_integer_windows():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_obv_sweep(jnp.ones((1, 64)), jnp.ones((1, 64)),
                              np.asarray([10.5]))


def test_fused_vwap_rejects_non_integer_windows():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_vwap_sweep(jnp.ones((1, 64)), jnp.ones((1, 64)),
                               np.asarray([10.5]), np.asarray([1.0]))


def test_fused_vwap_window_beyond_history():
    # A window larger than the padded history must not crash the static
    # slicing in the table prep; such lanes never pass warmup, so they must
    # match the generic path's all-flat result.
    _check_panel_sweep(
        "vwap_reversion", _vwap_call,
        dict(window=jnp.asarray([10.0, 150.0], jnp.float32),
             k=jnp.asarray([1.0], jnp.float32)), T=100, seed=23)


def test_fused_donchian_window_beyond_history():
    # A window larger than the (padded) history must not crash the shared
    # sparse-table prep; such lanes never pass warmup and must match the
    # generic all-flat result (window still within the generic view bound).
    ohlcv = data.synthetic_ohlcv(2, 100, seed=31)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(window=jnp.asarray([10.0, 200.0], jnp.float32))
    for strategy, call in (
            ("donchian", lambda: fused.fused_donchian_sweep(
                panel.close, np.asarray(grid["window"]), cost=1e-3)),
            ("donchian_hl", lambda: fused.fused_donchian_hl_sweep(
                panel.close, panel.high, panel.low,
                np.asarray(grid["window"]), cost=1e-3))):
        ref = sweep.jit_sweep(panel, get_strategy(strategy), dict(grid),
                              cost=1e-3)
        got = call()
        for name in ref._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(ref, name)),
                rtol=2e-4, atol=2e-5, err_msg=f"{strategy}/{name}")


def _touch_call(panel, grid, lens):
    return fused.fused_bollinger_touch_sweep(
        panel.close, np.asarray(grid["window"]), np.asarray(grid["k"]),
        t_real=lens, cost=1e-3)


def test_fused_bollinger_touch_matches_generic():
    _check_panel_sweep(
        "bollinger_touch", _touch_call,
        dict(window=jnp.asarray([10, 20, 30], jnp.float32),
             k=jnp.asarray([0.5, 1.0, 2.0], jnp.float32)), seed=33)


def test_fused_bollinger_touch_unaligned_T():
    # Known knife-edge case (failing since seed on jax 0.4.37): at this
    # (seed, T) exactly one cell's |z| - k margin sits at ~1e-7 relative,
    # and the XLA version's different fusion of the generic path's
    # z-score resolves the touch differently — the documented MXU/fusion
    # rounding class, not a regression. Assert the flip-budget contract
    # (the `bench --verify` discipline: rare flips, everything else
    # tight) instead of demanding bit-level agreement on a razor edge.
    ohlcv = data.synthetic_ohlcv(3, 251, seed=35)
    panel = type(ohlcv)(*(jnp.asarray(f) for f in ohlcv))
    grid = sweep.product_grid(window=jnp.asarray([8, 16], jnp.float32),
                              k=jnp.asarray([1.0, 1.5], jnp.float32))
    ref = sweep.jit_sweep(panel, get_strategy("bollinger_touch"),
                          dict(grid), cost=1e-3)
    got = _touch_call(panel, grid, None)
    _macd_flip_aware_check(got, ref)


def test_fused_bollinger_touch_ragged():
    _check_ragged(
        "bollinger_touch",
        lambda close, g, lens: fused.fused_bollinger_touch_sweep(
            close, np.asarray(g["window"]), np.asarray(g["k"]),
            t_real=lens, cost=1e-3),
        dict(window=jnp.asarray([10, 20], jnp.float32),
             k=jnp.asarray([1.0, 2.0], jnp.float32)),
        lengths=[180, 131, 256], seed=37)


def _stoch_call(panel, grid, lens):
    return fused.fused_stochastic_sweep(
        panel.close, panel.high, panel.low, np.asarray(grid["window"]),
        np.asarray(grid["band"]), t_real=lens, cost=1e-3)


def test_fused_stochastic_matches_generic():
    _check_panel_sweep(
        "stochastic", _stoch_call,
        dict(window=jnp.asarray([10, 14, 21], jnp.float32),
             band=jnp.asarray([20.0, 30.0], jnp.float32)), seed=41)


def test_fused_stochastic_unaligned_T():
    _check_panel_sweep(
        "stochastic", _stoch_call,
        dict(window=jnp.asarray([8, 16], jnp.float32),
             band=jnp.asarray([25.0], jnp.float32)), T=251, seed=43)


def test_fused_stochastic_ragged():
    _check_panel_ragged(
        "stochastic", _stoch_call,
        dict(window=jnp.asarray([10.0, 14.0], jnp.float32),
             band=jnp.asarray([20.0, 30.0], jnp.float32)),
        lengths=[150, 200, 97], seed=45)


def test_fused_stochastic_rejects_non_integer_windows():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_stochastic_sweep(
            jnp.ones((1, 64)), jnp.ones((1, 64)), jnp.ones((1, 64)),
            np.asarray([10.5]), np.asarray([20.0]))


def _keltner_call(panel, grid, lens):
    return fused.fused_keltner_sweep(
        panel.close, panel.high, panel.low, np.asarray(grid["window"]),
        np.asarray(grid["k"]), t_real=lens, cost=1e-3)


def test_fused_keltner_matches_generic():
    # The in-prep EMA ladder rounds differently from the generic
    # associative_scan (the RSI/MACD caveat); loosened tolerance only.
    _check_panel_sweep(
        "keltner", _keltner_call,
        dict(window=jnp.asarray([10, 14, 21], jnp.float32),
             k=jnp.asarray([1.5, 2.5], jnp.float32)), seed=47,
        rtol=2e-3, atol=2e-4)


def test_fused_keltner_unaligned_T():
    _check_panel_sweep(
        "keltner", _keltner_call,
        dict(window=jnp.asarray([8, 16], jnp.float32),
             k=jnp.asarray([2.0], jnp.float32)), T=251, seed=49,
        rtol=2e-3, atol=2e-4)


def test_fused_keltner_rejects_non_integer_windows():
    with pytest.raises(ValueError, match="integral"):
        fused.fused_keltner_sweep(
            jnp.ones((1, 64)), jnp.ones((1, 64)), jnp.ones((1, 64)),
            np.asarray([10.5]), np.asarray([1.5]))


# ---------------------------------------------------------------------------
# DBX_LANES_CAP: validation + in-process recompile (ADVICE.md findings)
# ---------------------------------------------------------------------------

def test_lanes_cap_rejects_off_ladder_values(monkeypatch):
    """A cap below 128 (or any non-ladder value) used to fall through to
    the FULL un-blocked P_pad — the opposite of a cap. It must raise."""
    for bad in ("64", "100", "1000", "abc", "-512"):
        monkeypatch.setenv("DBX_LANES_CAP", bad)
        with pytest.raises(ValueError, match="DBX_LANES_CAP"):
            fused.resolve_lanes_cap()
        with pytest.raises(ValueError, match="DBX_LANES_CAP"):
            fused.fused_sma_sweep(
                jnp.ones((1, 64)) + jnp.arange(64.0),
                np.asarray([3.0], np.float32), np.asarray([10.0], np.float32))


def test_lanes_cap_accepts_ladder_values(monkeypatch):
    # "0" is the explicit-disable sentinel, same as unset (old behavior)
    for good, want in (("128", 128), ("256", 256), ("512", 512),
                       ("1024", 1024), ("0", 0)):
        monkeypatch.setenv("DBX_LANES_CAP", good)
        assert fused.resolve_lanes_cap() == want
    monkeypatch.delenv("DBX_LANES_CAP")
    assert fused.resolve_lanes_cap() == 0


def test_widest_lanes_env_cap_never_unblocks():
    # env cap narrows sign-kernel calls; cap <= 256 calls ignore it
    assert fused._widest_lanes(1024, 512, 1280, env_cap=256) == 256
    assert fused._widest_lanes(1024, 512, 1280, env_cap=0) == 512
    assert fused._widest_lanes(1024, 256, 1280, env_cap=512) == 256


def test_lanes_cap_change_recompiles_in_process(monkeypatch):
    """The resolved cap is a static jit argument: changing DBX_LANES_CAP
    within one process must compile a NEW kernel, not silently reuse the
    stale lane width (the in-process A/B measured nothing before)."""
    monkeypatch.delenv("DBX_LANES_CAP", raising=False)
    close = np.cumsum(np.ones((2, 64), np.float32), axis=1) + 100.0
    fast = np.asarray([3.0, 3.0], np.float32)
    slow = np.asarray([10.0, 12.0], np.float32)
    m_default = fused.fused_sma_sweep(close, fast, slow)
    n_before = fused._fused_call._cache_size()
    monkeypatch.setenv("DBX_LANES_CAP", "512")
    m_capped = fused.fused_sma_sweep(close, fast, slow)
    assert fused._fused_call._cache_size() == n_before + 1
    # identical numerics either way — the cap changes blocking, not math
    for name in m_default._fields:
        np.testing.assert_allclose(np.asarray(getattr(m_capped, name)),
                                   np.asarray(getattr(m_default, name)))
    # same setting again: cache hit, no further compile
    fused.fused_sma_sweep(close, fast, slow)
    assert fused._fused_call._cache_size() == n_before + 1


@pytest.mark.parametrize("family", ["pairs", "bollinger"])
def test_ragged_short_row_matches_its_standalone_run(family):
    """A short row in a ragged group centres its moments on its OWN real
    bars, not on the group's padded width: its Sharpe row equals the same
    ticker swept alone. (The pad used to shift the centering constant,
    and the rounding of every centred moment moved with it — on v5e about
    half of a ragged pairs group's cells flipped against the reference.)"""
    d = data.synthetic_ohlcv(4, 1260, seed=3)
    close = np.asarray(d.close)
    y, x = close[:2].copy(), close[2:].copy()
    y[1, 1100:], x[1, 1100:] = y[1, 1099], x[1, 1099]
    lens = np.asarray([1260, 1100], np.int32)
    if family == "pairs":
        g = sweep.product_grid(
            lookback=np.arange(10, 50, 2, dtype=np.float32),
            z_entry=np.asarray([0.5, 1.0, 1.5, 2.0, 2.5, 3.0], np.float32))

        def run(a, b, t_real=None):
            return fused.fused_pairs_sweep(
                a, b, np.asarray(g["lookback"]), np.asarray(g["z_entry"]),
                t_real=t_real, cost=1e-3).sharpe
        ragged = run(y, x, lens)
        alone = run(y[1:, :1100], x[1:, :1100])
    else:
        g = sweep.product_grid(
            window=np.arange(10, 50, 2, dtype=np.float32),
            k=np.asarray([0.5, 1.0, 1.5, 2.0, 2.5, 3.0], np.float32))

        def run(a, t_real=None):
            return fused.fused_bollinger_sweep(
                a, np.asarray(g["window"]), np.asarray(g["k"]),
                t_real=t_real, cost=1e-3).sharpe
        ragged = run(y, lens)
        alone = run(y[1:, :1100])
    # Rounding still differs (a masked sum is not jnp.mean's reduction
    # tree); what must not differ is any position path: no cell moves by
    # the verify budget's flip threshold.
    np.testing.assert_allclose(np.asarray(ragged)[1:], np.asarray(alone),
                               rtol=verify.FLIP_REL, atol=1e-4)


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_interpret_default_follows_the_platform(monkeypatch, platform,
                                                want):
    """Interpret mode only on the CPU, compiled on the TPU, and an error
    anywhere else — never a silent interpret-mode run off the chip."""
    monkeypatch.setattr(fused.jax, "default_backend", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            fused.interpret_default()
    else:
        assert fused.interpret_default() is want
