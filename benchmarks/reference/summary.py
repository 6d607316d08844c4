"""The nine summary metrics of a backtest, written from their definitions.

Plain numpy, imports nothing of the system under test. ``dt`` is the
precision every operation runs in: float64 for the reference, a lower
precision (``ml_dtypes.bfloat16``) for the control that shows the
comparison can fail.

Definitions (``net`` is the per-bar net return, ``pos`` the position
decided at each bar's close, both ``(..., T)``; the equity curve is
additive, ``1 + cumsum(net)``; moments are population moments over all
``T`` bars; ``eps = 1e-12``):

- sharpe = mean(net) / (std(net) + eps) * sqrt(ppy)
- sortino = mean(net) / (sqrt(mean(min(net, 0)^2)) + eps) * sqrt(ppy)
- max_drawdown = max_t (peak_t - eq_t) / max(peak_t, eps), peak the
  running maximum of the equity curve
- total_return = eq_T - 1
- cagr = max(eq_T, eps) ^ (1 / max(T / ppy, eps)) - 1
- volatility = std(net) * sqrt(ppy)
- hit_rate = #(net > 0 and pos_{t-1} != 0) / (#(pos_{t-1} != 0) + eps)
- n_trades = turnover / 2
- turnover = sum_t |pos_t - pos_{t-1}|, pos_{-1} = 0
"""

from __future__ import annotations

import numpy as np

# Row order of a result block on the wire.
METRICS = ("sharpe", "sortino", "max_drawdown", "total_return", "cagr",
           "volatility", "hit_rate", "n_trades", "turnover")


def lagged(x: np.ndarray) -> np.ndarray:
    """``x[..., t-1]`` with 0 at ``t = 0``."""
    out = np.zeros_like(x)
    out[..., 1:] = x[..., :-1]
    return out


def simple_returns(close: np.ndarray) -> np.ndarray:
    """``close[t] / close[t-1] - 1``, and 0 at ``t = 0``."""
    r = np.zeros_like(close)
    r[..., 1:] = close[..., 1:] / close[..., :-1] - close.dtype.type(1)
    return r


def summary(net: np.ndarray, pos: np.ndarray, *, periods_per_year: int,
            dt) -> np.ndarray:
    """``(9, ...)`` metrics of ``net`` and ``pos`` in :data:`METRICS`
    order, every operation in ``dt``."""
    t = dt(net.shape[-1])
    eps = dt(1e-12)
    one = dt(1)
    ann = np.sqrt(dt(periods_per_year))
    mean = net.sum(axis=-1, dtype=dt) / t
    dev = net - mean[..., None]
    std = np.sqrt((dev * dev).sum(axis=-1, dtype=dt) / t)
    down = np.minimum(net, dt(0))
    dstd = np.sqrt((down * down).sum(axis=-1, dtype=dt) / t)
    equity = one + np.cumsum(net, axis=-1, dtype=dt)
    peak = np.maximum.accumulate(equity, axis=-1)
    mdd = ((peak - equity) / np.maximum(peak, eps)).max(axis=-1)
    final = equity[..., -1]
    years = dt(max(net.shape[-1] / periods_per_year, 1e-12))
    cagr = np.power(np.maximum(final, eps), one / years) - one
    prev = lagged(pos)
    active = prev != 0
    wins = (net > 0) & active
    hit = wins.sum(axis=-1).astype(dt) / (active.sum(axis=-1).astype(dt)
                                          + eps)
    turnover = np.abs(pos - prev).sum(axis=-1, dtype=dt)
    return np.stack([mean / (std + eps) * ann, mean / (dstd + eps) * ann,
                     mdd, final - one, cagr, std * ann, hit,
                     turnover * dt(0.5), turnover]).astype(dt)
