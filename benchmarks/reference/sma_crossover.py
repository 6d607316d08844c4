"""Plain reference of the SMA-crossover sweep, and its work count.

Long (+1) when the fast simple moving average of the close is above the
slow one, short (-1) when below, flat (0) until both averages are
defined (bar ``t >= max(fast, slow) - 1``). The position decided at bar
``t`` earns bar ``t + 1``'s simple return; a change of position costs
``cost * |pos_t - pos_{t-1}|``:

    net_t = pos_{t-1} * r_t - cost * |pos_t - pos_{t-1}|

and :mod:`summary` turns ``net`` and ``pos`` into the nine metrics. A
job's grid is the cartesian product of its axes, in the order of the
axis names (``fast`` outer, ``slow`` inner), as the result block lays
out its columns.
"""

from __future__ import annotations

import numpy as np

from reference.summary import lagged, simple_returns, summary

FIELDS = ("close",)
AXES = ("fast", "slow")


def _sma_rows(close: np.ndarray, windows, dt) -> np.ndarray:
    """``(len(windows), T)`` simple moving averages (0 before defined)."""
    T = close.shape[-1]
    cs = np.concatenate([np.zeros(1, dt), np.cumsum(close, dtype=dt)])
    out = np.zeros((len(windows), T), dt)
    for i, w in enumerate(windows):
        out[i, w - 1:] = (cs[w:] - cs[:T - w + 1]) / dt(w)
    return out


def sweep(legs, axes, *, cost: float, periods_per_year: int,
          dt=np.float64) -> np.ndarray:
    """``(9, P)`` metrics of one ticker over the grid ``axes`` (1-D
    arrays per axis); ``legs`` holds one mapping of field -> ``(T,)``."""
    close = np.asarray(legs[0]["close"]).astype(dt)
    T = close.shape[-1]
    fast = np.rint(np.asarray(axes["fast"], np.float64)).astype(int)
    slow = np.rint(np.asarray(axes["slow"], np.float64)).astype(int)
    sf = _sma_rows(close, fast, dt)
    ss = _sma_rows(close, slow, dt)
    diff = (sf[:, None, :] - ss[None, :, :]).reshape(-1, T)
    start = np.maximum(fast[:, None], slow[None, :]).reshape(-1) - 1
    valid = np.arange(T)[None, :] >= start[:, None]
    pos = np.where(valid, np.sign(diff), dt(0)).astype(dt)
    prev = lagged(pos)
    r = simple_returns(close)
    net = prev * r[None, :] - dt(cost) * np.abs(pos - prev)
    return summary(net, pos, periods_per_year=periods_per_year, dt=dt)


# Operations the reference's equations take, itemised so a reader can
# audit them. The count is of the algorithm, fixed whatever implements it.
PER_SERIES_BAR = {"simple return": 2, "prefix sum": 1}
PER_WINDOW_BAR = {"window difference": 1, "divide by window": 1}
PER_CELL_BAR = {
    "fast - slow": 1, "sign": 1, "warm-up select": 1,
    "pos_{t-1} * r_t": 1, "pos_t - pos_{t-1}": 1, "abs": 1,
    "cost * turnover": 1, "net": 1,
    # summary: mean, deviation, its square and sum, downside and its
    # square and sum, equity prefix sum, running peak, drawdown
    # (difference, floor, divide, max), active, win, both, two counts,
    # turnover sum.
    "sum net": 1, "deviation": 1, "square + sum": 2, "downside": 1,
    "downside square + sum": 2, "equity prefix sum": 1, "running peak": 1,
    "drawdown": 4, "active": 1, "win": 1, "win and active": 1,
    "counts": 2, "turnover sum": 1,
}


def work(n_jobs: int, bars: int, axes) -> tuple[float, float]:
    """``(operations, bytes)`` the algorithm needs for ``n_jobs`` tickers
    of ``bars`` bars over the grid ``axes``: the close read once, the
    nine f32 metrics of every cell written once."""
    n_windows = len(set(np.asarray(axes["fast"]).tolist())) + \
        len(set(np.asarray(axes["slow"]).tolist()))
    combos = int(np.prod([np.asarray(v).size for v in axes.values()]))
    ops = n_jobs * bars * (sum(PER_SERIES_BAR.values())
                           + n_windows * sum(PER_WINDOW_BAR.values())
                           + combos * sum(PER_CELL_BAR.values()))
    nbytes = n_jobs * (bars * 4 * len(FIELDS) + combos * 9 * 4)
    return float(ops), float(nbytes)
