"""Walk-forward optimization (``BASELINE.json`` configs[4]).

Classic out-of-sample protocol: slide a (train, test) window over the bar
history; per window, evaluate the full parameter grid on the train span, pick
the best parameter per ticker, then realize that parameter's returns on the
held-out test span. The TPU shape of this is ``lax.scan`` over refit windows
(sequential by construction — window w+1's start depends only on the
schedule, but scanning keeps one compiled program) with the full
(ticker x param) ``vmap`` sweep *nested inside* each step — SURVEY.md §7's
"lax.scan over refit windows + nested vmap".

All shapes are static: every window is ``train + test`` bars long, sliced
with ``lax.dynamic_slice`` at traced offsets; train/test membership is a
mask, not a shape.
"""

from __future__ import annotations

import functools
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp

from ..models.base import Strategy
from ..ops import metrics as metrics_mod
from ..ops import pnl as pnl_mod

Array = jax.Array


class WalkForwardResult(NamedTuple):
    """Outputs of a walk-forward run.

    Attributes:
        oos_returns: ``(n_tickers, n_windows * test)`` stitched out-of-sample
            net returns under the per-window chosen params, including the
            rebalance cost at window boundaries.
        oos_positions: ``(n_tickers, n_windows * test)`` stitched positions.
        oos_metrics: :class:`~..ops.metrics.Metrics` over the stitched series,
            each field ``(n_tickers,)`` — the honest performance estimate.
        chosen: dict param name -> ``(n_tickers, n_windows)`` selected values.
        train_metric: ``(n_tickers, n_windows)`` best in-sample metric value.
    """

    oos_returns: Array
    oos_positions: Array
    oos_metrics: metrics_mod.Metrics
    chosen: Mapping[str, Array]
    train_metric: Array


def window_starts_np(T: int, train: int, test: int):
    """Anchored-walk schedule, host-side numpy: windows advance by
    ``test`` bars. Number of windows is ``(T - train) // test`` — every
    test bar is covered at most once, and only bars with a full train
    span behind them are used. The ONE schedule definition: the generic
    scan and the fused two-phase route both derive from here (the fused
    route needs host values — a jnp array would be a tracer inside the
    worker's shard_map body)."""
    import numpy as np

    n = (T - train) // test
    if n <= 0:
        raise ValueError(f"history T={T} too short for train={train} test={test}")
    return np.arange(n) * test


def window_starts(T: int, train: int, test: int) -> jnp.ndarray:
    """:func:`window_starts_np` as a jnp array (the scan-carry form)."""
    return jnp.asarray(window_starts_np(T, train, test))


@functools.partial(
    jax.jit,
    static_argnames=("strategy", "train", "test", "metric", "periods_per_year"))
def walk_forward(
    ohlcv,
    strategy: Strategy,
    grid: Mapping[str, Array],
    *,
    train: int,
    test: int,
    metric: str = "sharpe",
    cost: float = 0.0,
    periods_per_year: int = 252,
) -> WalkForwardResult:
    """Run walk-forward optimization over a ``(n_tickers, T)`` OHLCV panel.

    Per window (scanned): slice ``train + test`` bars, sweep the grid with
    metrics masked to the train span, argmax per ticker, re-price the winning
    param with returns masked to the test span. The per-window sweep reuses
    the same fused (ticker x param) kernel as :func:`~.sweep.run_sweep`.
    """
    T = ohlcv.close.shape[-1]
    starts = window_starts(T, train, test)
    n_tickers = ohlcv.close.shape[0]
    span = train + test
    sign = metrics_mod.metric_sign(metric)

    def slice_win(a, s0):
        return jax.lax.dynamic_slice_in_dim(a, s0, span, axis=-1)

    def one_window(carry, s0):
        win = type(ohlcv)(*(slice_win(f, s0) for f in ohlcv))

        def per_param(ohlcv_1, params):
            pos = strategy.positions(ohlcv_1, params)
            res = pnl_mod.backtest_prefix(ohlcv_1.close, pos, cost=cost)
            # Positions at bar t use only bars <= t, so the full-window series
            # sliced to [:train] is identical to a train-only run — the train
            # metric sees *statically* train-span returns/equity/positions
            # (no test-span leakage for equity-based metrics either).
            train_m = getattr(metrics_mod.summary_metrics(
                res.returns[..., :train], res.equity[..., :train],
                res.positions[..., :train],
                periods_per_year=periods_per_year), metric)
            return (train_m, res.returns[..., train:],
                    res.positions[..., train:], res.positions[..., train - 1])

        def per_ticker(ohlcv_1):
            train_m, rets, poss, prevs = jax.vmap(
                lambda p: per_param(ohlcv_1, p))(dict(grid))  # (P,),(P,test)..
            best = jnp.argmax(sign * train_m)
            return train_m[best], best, rets[best], poss[best], prevs[best]

        best_val, best_idx, oos_r, oos_p, prev_in = jax.vmap(per_ticker)(win)
        rf = win.close[:, train] / win.close[:, train - 1] - 1.0
        return carry, (best_val, best_idx, oos_r, oos_p, prev_in, rf)

    _, (train_best, best_idx, oos_r, oos_p, prev_in, rf) = jax.lax.scan(
        one_window, 0, starts)
    # scan outputs are window-major: (n_windows, n_tickers, ...)
    chosen = {k: jnp.moveaxis(jnp.take(v, best_idx), 0, 1)
              for k, v in grid.items()}
    return _stitch(oos_r, oos_p, prev_in, rf, train_best, chosen,
                   n_tickers=n_tickers, cost=cost,
                   periods_per_year=periods_per_year)


@functools.partial(
    jax.jit,
    static_argnames=("train", "test", "metric", "periods_per_year"))
def walk_forward_pairs(
    y_close,
    x_close,
    grid: Mapping[str, Array],
    *,
    train: int,
    test: int,
    metric: str = "sharpe",
    cost: float = 0.0,
    periods_per_year: int = 252,
) -> WalkForwardResult:
    """Walk-forward optimization for the two-legged pairs strategy.

    Same protocol and scan structure as :func:`walk_forward` over
    ``(n_pairs, T)`` leg panels: per refit window, sweep the
    (lookback, z_entry[, z_exit]) grid on the train span (rolling OLS +
    z-score + band machine recomputed *within* the window — positions at
    bar t use only bars <= t, so span-slice train metrics equal a
    train-only run), argmax per pair, realize the winner on the test span.
    The stitched boundary fix-up replaces the single-asset underlying
    return with the window's *hedged* spread return factor at its first
    OOS bar — i.e. the deployed sequence re-hedges each window with the
    incoming window's chosen beta (positions carry over in spread units;
    ``models.pairs.pair_backtest`` cost semantics throughout).
    """
    from ..models import pairs as pairs_mod

    T = y_close.shape[-1]
    starts = window_starts(T, train, test)
    n_pairs = y_close.shape[0]
    span = train + test
    sign = metrics_mod.metric_sign(metric)

    def slice_win(a, s0):
        return jax.lax.dynamic_slice_in_dim(a, s0, span, axis=-1)

    def one_window(carry, s0):
        ywin = slice_win(y_close, s0)
        xwin = slice_win(x_close, s0)

        def per_param(y1, x1, params):
            # The one semantics-defining PnL (shared with run_pairs_sweep
            # via pair_backtest), so train metrics cannot drift from the
            # sweep's.
            pos, net, hr = pairs_mod.pair_net_returns(y1, x1, params,
                                                      cost=cost)
            equity_tr = 1.0 + jnp.cumsum(net[..., :train], axis=-1)
            train_m = getattr(metrics_mod.summary_metrics(
                net[..., :train], equity_tr, pos[..., :train],
                periods_per_year=periods_per_year), metric)
            return (train_m, net[..., train:], pos[..., train:],
                    pos[..., train - 1], hr[..., train])

        def per_pair(y1, x1):
            train_m, rets, poss, prevs, hrf = jax.vmap(
                lambda p: per_param(y1, x1, p))(dict(grid))
            best = jnp.argmax(sign * train_m)
            return (train_m[best], best, rets[best], poss[best],
                    prevs[best], hrf[best])

        best_val, best_idx, oos_r, oos_p, prev_in, hrf = jax.vmap(
            per_pair)(ywin, xwin)
        return carry, (best_val, best_idx, oos_r, oos_p, prev_in, hrf)

    _, (train_best, best_idx, oos_r, oos_p, prev_in, hrf) = jax.lax.scan(
        one_window, 0, starts)
    chosen = {k: jnp.moveaxis(jnp.take(v, best_idx), 0, 1)
              for k, v in grid.items()}
    return _stitch(oos_r, oos_p, prev_in, hrf, train_best, chosen,
                   n_tickers=n_pairs, cost=cost,
                   periods_per_year=periods_per_year)


def _stitch(oos_r, oos_p, prev_in, rf, train_best, chosen, *, n_tickers,
            cost, periods_per_year) -> WalkForwardResult:
    """Window-major per-window outputs -> stitched WalkForwardResult.

    Boundary fix-up: each window's first OOS bar was priced by
    backtest_prefix against that window's own train-span position at
    ``train-1`` (``prev_in``): it earned ``prev_in * r`` and paid turnover
    ``|pos - prev_in|``. A sequential deployment instead carries the
    *previous window's* final OOS position into that bar (window w's last
    test bar is the bar before window w+1's first one) — and starts flat at
    window 0. Swap both the earnings and the cost terms so the stitched
    series prices exactly the positions it reports.
    """
    first_pos = oos_p[:, :, 0]                                # (W, n_tickers)
    prev_deployed = jnp.concatenate(
        [jnp.zeros_like(first_pos[:1]), oos_p[:-1, :, -1]], axis=0)
    c = jnp.asarray(cost, oos_r.dtype)
    adj = (prev_deployed - prev_in) * rf - c * (
        jnp.abs(first_pos - prev_deployed) - jnp.abs(first_pos - prev_in))
    oos_r = oos_r.at[:, :, 0].add(adj)

    oos_returns = jnp.moveaxis(oos_r, 0, 1).reshape(n_tickers, -1)
    oos_positions = jnp.moveaxis(oos_p, 0, 1).reshape(n_tickers, -1)
    equity = 1.0 + jnp.cumsum(oos_returns, axis=-1)
    oos_metrics = metrics_mod.summary_metrics(
        oos_returns, equity, oos_positions,
        periods_per_year=periods_per_year)
    return WalkForwardResult(
        oos_returns=oos_returns,
        oos_positions=oos_positions,
        oos_metrics=oos_metrics,
        chosen=chosen,
        train_metric=jnp.moveaxis(train_best, 0, 1),
    )


@functools.partial(jax.jit, static_argnames=("starts", "train"))
def _stack_train_windows(close, starts: tuple, train: int):
    """All windows' train slices as one ``(W * n_tickers, train)`` panel."""
    rows = [jax.lax.dynamic_slice_in_dim(close, s0, train, axis=-1)
            for s0 in starts]
    stacked = jnp.stack(rows)                            # (W, N, train)
    return stacked.reshape(-1, train)


@functools.partial(jax.jit, static_argnames=("W", "n_tickers"))
def _window_argmax(vals, sign, W: int, n_tickers: int):
    """(W*N, P) metric values -> per-(window, ticker) argmax index + value."""
    v = vals.reshape(W, n_tickers, -1)
    idx = jnp.argmax(sign * v, axis=-1)
    best = jnp.take_along_axis(v, idx[..., None], -1)[..., 0]
    return idx, best


@functools.partial(
    jax.jit,
    static_argnames=("strategy", "train", "test", "periods_per_year"))
def _reprice_chosen(ohlcv, strategy: Strategy, chosen_per_window, starts, *,
                    train: int, test: int, cost=0.0,
                    periods_per_year: int = 252):
    """Phase 2 of the fused walk-forward: re-price each ticker's CHOSEN
    param per window (P=1 per ticker — the cheap part)."""
    span = train + test

    def slice_win(a, s0):
        return jax.lax.dynamic_slice_in_dim(a, s0, span, axis=-1)

    def one_window(carry, inp):
        s0, params_n = inp
        win = type(ohlcv)(*(slice_win(f, s0) for f in ohlcv))

        def per_ticker(ohlcv_1, p1):
            pos = strategy.positions(ohlcv_1, p1)
            res = pnl_mod.backtest_prefix(ohlcv_1.close, pos, cost=cost)
            return (res.returns[..., train:], res.positions[..., train:],
                    res.positions[..., train - 1])

        oos_r, oos_p, prev_in = jax.vmap(per_ticker)(win, params_n)
        rf = win.close[:, train] / win.close[:, train - 1] - 1.0
        return carry, (oos_r, oos_p, prev_in, rf)

    _, outs = jax.lax.scan(one_window, 0, (starts, chosen_per_window))
    return outs


def walk_forward_fused(
    ohlcv,
    strategy: Strategy,
    grid: Mapping[str, Array],
    train_metrics_fn,
    *,
    train: int,
    test: int,
    metric: str = "sharpe",
    cost: float = 0.0,
    periods_per_year: int = 252,
    fields: tuple = ("close",),
) -> WalkForwardResult:
    """Walk-forward with the TRAIN sweep on a fused Pallas kernel.

    The expensive phase — the full (ticker x param) grid per refit window —
    runs as ``train_metrics_fn(*field_slices) -> Metrics`` (e.g. a
    ``functools.partial`` of :func:`~..ops.fused.fused_sma_sweep` with the
    flat grid arrays bound); ``fields`` names the OHLCV columns the kernel
    consumes, in its positional order (``("close",)`` for the single-series
    families, ``("close", "high", "low")`` for the channel families, …).
    Only each ticker's argmax-chosen param is then re-priced over the
    (train+test) span, and the stitched result uses the same boundary
    fix-up as :func:`walk_forward`. Results match :func:`walk_forward`
    exactly wherever the fused and generic train metrics agree on the
    argmax (knife-edge metric ties can flip a chosen param — the caveat
    class ``bench.py --verify`` quantifies).
    """
    import numpy as np

    T = ohlcv.close.shape[-1]
    starts_np = window_starts_np(T, train, test)
    n_tickers = ohlcv.close.shape[0]
    W = len(starts_np)
    sign = metrics_mod.metric_sign(metric)

    # Phase 1: ONE fused train sweep over all windows at once — the W
    # train slices (of every field the kernel consumes) stack into
    # (W * n_tickers, train) panels so the whole phase is a single kernel
    # launch (a per-window python loop was ~5x slower end to end: every
    # eager slice/argmax op pays its own host dispatch).
    starts_tup = tuple(int(s) for s in starts_np)
    stacked = [_stack_train_windows(getattr(ohlcv, f), starts_tup, train)
               for f in fields]
    m = train_metrics_fn(*stacked)                       # (W*N, P) fields
    best_idx, train_best = _window_argmax(
        getattr(m, metric), sign, W, n_tickers)          # (W, N) each

    chosen_per_window = {k: jnp.take(jnp.asarray(v), best_idx)
                         for k, v in grid.items()}       # (W, n_tickers)
    oos_r, oos_p, prev_in, rf = _reprice_chosen(
        ohlcv, strategy, chosen_per_window, jnp.asarray(starts_np),
        train=train, test=test, cost=cost,
        periods_per_year=periods_per_year)
    chosen = {k: jnp.moveaxis(v, 0, 1)
              for k, v in chosen_per_window.items()}
    return _stitch(oos_r, oos_p, prev_in, rf, train_best, chosen,
                   n_tickers=n_tickers, cost=cost,
                   periods_per_year=periods_per_year)
