"""Plain reference of the rolling-OLS pairs sweep, and its work count.

A job is a pair of close series ``(y, x)`` and a grid of ``lookback``
(bars, ``w``) and ``z_entry``. At each bar ``t >= w - 1`` an ordinary
least-squares fit of ``y`` on ``x`` (with intercept) over the trailing
``w`` bars gives the hedge ratio

    beta_t = cov_w(x, y) / (var_w(x) + eps)      (sums over the window,
                                                  not divided by w)

and the spread is the fit's residual at ``t``,
``(y_t - mean_w(y)) - beta_t (x_t - mean_w(x))``. The spread's z-score
over the same trailing ``w`` bars, ``(s_t - mean_w(s)) / (std_w(s) +
eps)`` (population std), is defined from ``t >= 2w - 2``, when the whole
window holds fitted spreads; before that the machine is flat. The
machine enters long (+1) when ``z < -z_entry``, short (-1) when ``z >
z_entry``, leaves a long when ``z >= -z_exit`` and a short when ``z <=
z_exit`` (``z_exit = 0``), and never goes from long to short without
passing through flat. Holding the position decided at ``t - 1`` earns
the gross-normalised spread return

    hr_t = (ry_t - beta_{t-1} rx_t) / max(1 + |beta_{t-1}|, 1)

(``beta = 0`` before the first fit), and

    net_t = pos_{t-1} * hr_t - cost * |pos_t - pos_{t-1}|.

:mod:`summary` turns ``net`` and ``pos`` into the nine metrics; columns
run ``lookback`` outer, ``z_entry`` inner.
"""

from __future__ import annotations

import numpy as np

from reference.summary import lagged, simple_returns, summary

FIELDS = ("close",)
LEGS = 2
AXES = ("lookback", "z_entry")
Z_EXIT = 0.0
EPS = 1e-12


def _window_sum(a: np.ndarray, w: int, dt) -> np.ndarray:
    """Trailing ``w``-bar sums of ``a`` (valid from ``t >= w - 1``)."""
    T = a.shape[-1]
    cs = np.concatenate([np.zeros(1, dt), np.cumsum(a, dtype=dt)])
    out = np.zeros(T, dt)
    out[w - 1:] = cs[w:] - cs[:T - w + 1]
    return out


def _signals(y, x, w: int, dt):
    """``(beta, z, valid)`` for one lookback, each ``(T,)``."""
    T = y.shape[-1]
    wd = dt(w)
    xc = x - x.sum(dtype=dt) / dt(T)
    yc = y - y.sum(dtype=dt) / dt(T)
    sx = _window_sum(xc, w, dt)
    sy = _window_sum(yc, w, dt)
    sxx = _window_sum(xc * xc, w, dt)
    sxy = _window_sum(xc * yc, w, dt)
    cov = sxy - sx * sy / wd
    var = np.maximum(sxx - sx * sx / wd, dt(0))
    fitted = np.arange(T) >= w - 1
    beta = np.where(fitted, cov / (var + dt(EPS)), dt(0))
    resid = np.where(fitted, (yc - sy / wd) - beta * (xc - sx / wd), dt(0))
    s1 = _window_sum(resid, w, dt)
    s2 = _window_sum(resid * resid, w, dt)
    m = s1 / wd
    sd = np.sqrt(np.maximum(s2 / wd - m * m, dt(0)))
    valid = np.arange(T) >= 2 * w - 2
    z = np.where(valid, (resid - m) / (sd + dt(EPS)), dt(0))
    return beta.astype(dt), z.astype(dt), valid


def _band_positions(z: np.ndarray, valid: np.ndarray, z_entry: np.ndarray,
                    dt) -> np.ndarray:
    """``(L, Z, T)`` positions of the band machine for ``z``/``valid``
    ``(L, T)`` and entry thresholds ``z_entry`` ``(Z,)``."""
    L, T = z.shape
    ze = z_entry.astype(dt)[None, :]
    zx = dt(Z_EXIT)
    pos = np.zeros((L, z_entry.size), dt)
    out = np.zeros((L, z_entry.size, T), dt)
    one, zero = dt(1), dt(0)
    for t in range(T):
        zt = z[:, t:t + 1]
        entered = np.where(zt < -ze, one, np.where(zt > ze, -one, zero))
        leave = ((pos > 0) & (zt >= -zx)) | ((pos < 0) & (zt <= zx))
        held = np.where(leave, zero, pos)
        pos = np.where(pos == 0, entered, held)
        pos = np.where(valid[:, t:t + 1], pos, zero).astype(dt)
        out[:, :, t] = pos
    return out


def sweep(legs, axes, *, cost: float, periods_per_year: int,
          dt=np.float64) -> np.ndarray:
    """``(9, P)`` metrics of one pair ``legs = (y, x)`` (mappings of
    field -> ``(T,)``) over the grid ``axes``."""
    y = np.asarray(legs[0]["close"]).astype(dt)
    x = np.asarray(legs[1]["close"]).astype(dt)
    T = y.shape[-1]
    lbs = np.rint(np.asarray(axes["lookback"], np.float64)).astype(int)
    ze = np.asarray(axes["z_entry"], np.float64)
    sig = [_signals(y, x, int(w), dt) for w in lbs]
    beta = np.stack([s[0] for s in sig])                   # (L, T)
    z = np.stack([s[1] for s in sig])
    valid = np.stack([s[2] for s in sig])
    pos = _band_positions(z, valid, ze, dt)                # (L, Z, T)
    ry, rx = simple_returns(y), simple_returns(x)
    pb = lagged(beta)
    hr = (ry[None, :] - pb * rx[None, :]) / np.maximum(dt(1) + np.abs(pb),
                                                       dt(1))
    prev = lagged(pos)
    net = prev * hr[:, None, :] - dt(cost) * np.abs(pos - prev)
    out = summary(net.reshape(-1, T), pos.reshape(-1, T),
                  periods_per_year=periods_per_year, dt=dt)
    return out


# Operations of the reference's equations, itemised (see
# sma_crossover.py). Per (pair, lookback, bar): centring, four window
# sums, covariance and variance, the fit, the residual, its window mean
# and deviation, the z-score, the hedged return.
PER_LOOKBACK_BAR = {
    "centre legs": 2, "products": 2, "prefix sums": 4, "window differences": 4,
    "covariance + variance": 6, "beta": 2, "residual": 5,
    "spread moments": 6, "std": 3, "z-score": 3,
    "hedged return": 6,
}
PER_CELL_BAR = {
    "band machine": 8, "pos_{t-1} * hr_t": 1, "turnover": 2,
    "cost * turnover": 1, "net": 1,
    "sum net": 1, "deviation": 1, "square + sum": 2, "downside": 1,
    "downside square + sum": 2, "equity prefix sum": 1, "running peak": 1,
    "drawdown": 4, "active": 1, "win": 1, "win and active": 1,
    "counts": 2, "turnover sum": 1,
}
PER_SERIES_BAR = {"simple return": 2}


def work(n_jobs: int, bars: int, axes) -> tuple[float, float]:
    """``(operations, bytes)`` for ``n_jobs`` pairs of ``bars`` bars over
    the grid ``axes``: both legs' closes read once, nine f32 metrics per
    cell written once."""
    n_lb = len(set(np.asarray(axes["lookback"]).tolist()))
    combos = int(np.prod([np.asarray(v).size for v in axes.values()]))
    ops = n_jobs * bars * (LEGS * sum(PER_SERIES_BAR.values())
                           + n_lb * sum(PER_LOOKBACK_BAR.values())
                           + combos * sum(PER_CELL_BAR.values()))
    nbytes = n_jobs * (LEGS * bars * 4 * len(FIELDS) + combos * 9 * 4)
    return float(ops), float(nbytes)
