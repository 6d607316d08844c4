"""Plain reference of the VWAP-deviation reversion sweep, and its work count.

A job is one ticker's ``close`` and ``volume`` and a grid of ``window``
(bars, ``w``) and ``k``. The rolling VWAP over the trailing ``w`` bars is

    vwap_t = sum_w(close * volume) / (sum_w(volume) + eps)

(defined from ``t >= w - 1``; a window with no volume falls back to the
close), and the deviation ``dev_t = close_t - vwap_t`` is 0 before it is
defined. Its z-score over the same trailing ``w`` bars, ``(dev_t -
mean_w(dev)) / (std_w(dev) + eps)`` (population std), is defined from
``t >= 2w - 2``, when the whole window holds deviations; before that the
machine is flat. The band machine enters long (+1) when ``z < -k``,
short (-1) when ``z > k``, leaves a long when ``z >= -z_exit`` and a
short when ``z <= z_exit`` (``z_exit = 0``: price re-crosses its VWAP),
and never goes from long to short without passing through flat. The
position decided at bar ``t`` earns bar ``t + 1``'s simple return:

    net_t = pos_{t-1} * r_t - cost * |pos_t - pos_{t-1}|

and :mod:`summary` turns ``net`` and ``pos`` into the nine metrics.
Columns run ``k`` outer, ``window`` inner: the wire lays a result block
out over the grid's axes in the order of their names.

Computed in blocks, so a year of minute bars fits: the band machine is
one loop over the bars, vectorised over every (window, k) lane, keeping
the positions as int8; the PnL and the summary run a block of windows at
a time, the blocks on threads (numpy releases the interpreter lock in
them). Each row of the summary is reduced on its own, so the blocks give
exactly the numbers of one unblocked pass (``block_windows=None``).

Importing this module checks the cell's one precondition on the program
under test (:func:`harness.preflight.require_uncapped`): a program whose
router would send a year of minute bars to its generic (unfused) path
stops the run at once instead of stalling in set-up.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

from harness import preflight
from reference.summary import lagged, simple_returns, summary

FIELDS = ("close", "volume")
AXES = ("window", "k")
Z_EXIT = 0.0
EPS = 1e-12
# Windows per block of the PnL and summary (32 k-lanes each at the
# cell's grid: 2 x 32 x 98,280 float64 bars is 50 MB, ~0.5 GB live on
# each thread; 8-window blocks took the process to 9 GB at the same
# speed).
BLOCK_WINDOWS = 2
THREADS = min(5, os.cpu_count() or 1)
# A year of one-minute regular-session bars (390 x 252): the history the
# program has to take on its fused route.
MINUTE_YEAR_BARS = 98280


def _window_sum(cs: np.ndarray, w: int, dt) -> np.ndarray:
    """Trailing ``w``-bar sums (valid from ``t >= w - 1``, 0 before)
    from the prefix sums ``cs`` (``(T + 1,)``, ``cs[0] = 0``)."""
    T = cs.shape[-1] - 1
    out = np.zeros(T, dt)
    out[w - 1:] = cs[w:] - cs[:T - w + 1]
    return out


def _prefix(a: np.ndarray, dt) -> np.ndarray:
    return np.concatenate([np.zeros(1, dt), np.cumsum(a, dtype=dt)])


def _zscore(close, volume, cs_pv, cs_v, w: int, dt):
    """``(z, valid)`` of one window, each ``(T,)``."""
    T = close.shape[-1]
    t = np.arange(T)
    pv = _window_sum(cs_pv, w, dt)
    v = _window_sum(cs_v, w, dt)
    have = (t >= w - 1) & (v > dt(EPS))
    with np.errstate(divide="ignore", invalid="ignore"):
        dev = np.where(have, close - pv / (v + dt(EPS)), dt(0)).astype(dt)
    wd = dt(w)
    m = _window_sum(_prefix(dev, dt), w, dt) / wd
    s2 = _window_sum(_prefix(dev * dev, dt), w, dt) / wd
    sd = np.sqrt(np.maximum(s2 - m * m, dt(0)))
    valid = t >= 2 * w - 2
    z = np.where(valid, (dev - m) / (sd + dt(EPS)), dt(0))
    return z.astype(dt), valid


def _band_positions(z: np.ndarray, valid: np.ndarray, k: np.ndarray,
                    dt) -> np.ndarray:
    """``(T, W * K)`` int8 positions of the band machine for ``z`` /
    ``valid`` ``(W, T)`` and entry bands ``k`` ``(K,)``; lane ``i * K +
    j`` is window ``i``, band ``j``."""
    W, T = z.shape
    ke = k.astype(dt)[None, :]
    zx = dt(Z_EXIT)
    one, zero = dt(1), dt(0)
    pos = np.zeros((W, k.size), dt)
    out = np.zeros((T, W * k.size), np.int8)
    zt_all = np.ascontiguousarray(z.T)[:, :, None]          # (T, W, 1)
    ok_all = np.ascontiguousarray(valid.T)[:, :, None]
    for t in range(T):
        zt = zt_all[t]
        entered = np.where(zt < -ke, one, np.where(zt > ke, -one, zero))
        leave = ((pos > 0) & (zt >= -zx)) | ((pos < 0) & (zt <= zx))
        held = np.where(leave, zero, pos)
        pos = np.where(pos == 0, entered, held)
        pos = np.where(ok_all[t], pos, zero).astype(dt)
        out[t] = pos.reshape(-1)
    return out


def sweep(legs, axes, *, cost: float, periods_per_year: int,
          dt=np.float64, block_windows: int | None = BLOCK_WINDOWS
          ) -> np.ndarray:
    """``(9, P)`` metrics of one ticker ``legs = (fields,)`` (a mapping of
    field -> ``(T,)``) over the grid ``axes``, ``block_windows`` windows
    per summary block (None: all lanes in one pass)."""
    close = np.asarray(legs[0]["close"]).astype(dt)
    volume = np.asarray(legs[0]["volume"]).astype(dt)
    T = close.shape[-1]
    wins = np.rint(np.asarray(axes["window"], np.float64)).astype(int)
    k = np.asarray(axes["k"], np.float64)
    cs_pv = _prefix(close * volume, dt)
    cs_v = _prefix(volume, dt)
    sig = [_zscore(close, volume, cs_pv, cs_v, int(w), dt) for w in wins]
    z = np.stack([s[0] for s in sig])                      # (W, T)
    valid = np.stack([s[1] for s in sig])
    pos_all = _band_positions(z, valid, k, dt)             # (T, W * K)
    r = simple_returns(close)
    step = len(wins) if block_windows is None else int(block_windows)

    def block(w0):
        lanes = slice(w0 * k.size, min(w0 + step, len(wins)) * k.size)
        pos = np.ascontiguousarray(pos_all[:, lanes].T).astype(dt)
        prev = lagged(pos)
        net = prev * r[None, :] - dt(cost) * np.abs(pos - prev)
        return summary(net, pos, periods_per_year=periods_per_year, dt=dt)

    with concurrent.futures.ThreadPoolExecutor(
            min(THREADS, -(-len(wins) // step))) as pool:
        out = list(pool.map(block, range(0, len(wins), step)))
    out = np.concatenate(out, axis=1).reshape(9, len(wins), k.size)
    return np.ascontiguousarray(out.transpose(0, 2, 1)).reshape(9, -1)


# Operations of the reference's equations, itemised (see
# sma_crossover.py). Per (ticker, bar): the return, price x volume and
# the two prefix sums the VWAP windows difference.
PER_SERIES_BAR = {"simple return": 2, "price x volume": 1,
                  "prefix sums": 2}
# Per (ticker, window, bar): the VWAP, the deviation, its two moments'
# prefix sums and window sums, mean, variance, std and the z-score.
PER_WINDOW_BAR = {
    "window differences": 2, "vwap divide": 2, "deviation + select": 2,
    "deviation square": 1, "moment prefix sums": 2,
    "moment window differences": 2, "mean + second moment": 2,
    "variance": 2, "std": 2, "z-score": 3, "warm-up select": 1,
}
PER_CELL_BAR = {
    "band machine": 8, "pos_{t-1} * r_t": 1, "turnover": 2,
    "cost * turnover": 1, "net": 1,
    "sum net": 1, "deviation": 1, "square + sum": 2, "downside": 1,
    "downside square + sum": 2, "equity prefix sum": 1, "running peak": 1,
    "drawdown": 4, "active": 1, "win": 1, "win and active": 1,
    "counts": 2, "turnover sum": 1,
}


def work(n_jobs: int, bars: int, axes) -> tuple[float, float]:
    """``(operations, bytes)`` for ``n_jobs`` tickers of ``bars`` bars
    over the grid ``axes``: close and volume read once, nine f32 metrics
    per cell written once."""
    n_win = len(set(np.asarray(axes["window"]).tolist()))
    combos = int(np.prod([np.asarray(v).size for v in axes.values()]))
    ops = n_jobs * bars * (sum(PER_SERIES_BAR.values())
                           + n_win * sum(PER_WINDOW_BAR.values())
                           + combos * sum(PER_CELL_BAR.values()))
    nbytes = n_jobs * (bars * 4 * len(FIELDS) + combos * 9 * 4)
    return float(ops), float(nbytes)


preflight.require_uncapped("vwap_reversion", MINUTE_YEAR_BARS)
