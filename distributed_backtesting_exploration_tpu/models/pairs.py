"""Rolling-OLS pairs trade (``BASELINE.json`` configs[3]).

A pair is (y, x) close series. Per bar: a rolling OLS of y on x gives the
hedge ratio ``beta``; the spread ``y - (alpha + beta x)`` is z-scored over the
same lookback; the machine enters a unit spread position when ``|z|`` exceeds
``z_entry`` and exits when z re-crosses ``z_exit`` (hysteresis, evaluated in
log depth via the associative band machine).
Spread return per bar is ``pos[t-1] * (r_y[t] - beta[t-1] * r_x[t]) / (1 + |beta|)``
(gross exposure normalized), with cost charged on both legs' turnover.

Pairs don't fit the single-asset :class:`~.base.Strategy` seam (two inputs),
so this module owns its sweep entry point :func:`run_pairs_sweep`, vmapped
over (pair x param) exactly like the single-asset engine — one fused XLA
program per job.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops import metrics as metrics_mod
from ..ops import pnl as pnl_mod
from ..ops import rolling, signals

Array = jax.Array


def pair_signals(y: Array, x: Array, lookback):
    """Rolling hedge ratio and spread z-score for one pair; ``(T,)`` each.

    ``lookback`` may be traced (vmap over lookback grids).
    """
    beta, resid = rolling.rolling_ols_residual(y, x, lookback, fill=0.0)
    # Warmup spread is y - (0 + 0 x) = y.
    spread = jnp.where(rolling.valid_mask(y.shape[-1], lookback), resid, y)
    z = rolling.rolling_zscore(spread, lookback, fill=0.0)
    # The spread itself needs `lookback` bars of OLS warmup, and its z-score
    # another `lookback`; mask both.
    valid = rolling.valid_mask(y.shape[-1], 2 * jnp.asarray(lookback) - 1)
    return beta, jnp.where(valid, z, 0.0), valid


def pairs_positions(y: Array, x: Array, params) -> tuple[Array, Array]:
    """Stateful entry/exit machine; returns ``(pos, beta)`` each ``(T,)``.

    pos = +1: long spread (long y, short beta*x); -1: short spread; 0 flat.
    Shares the band-hysteresis scan with Bollinger mean-reversion.
    """
    beta, z, valid = pair_signals(y, x, params["lookback"])
    pos = signals.band_hysteresis_assoc(
        z, valid, params["z_entry"], params.get("z_exit", 0.0))
    return pos, beta


def pair_net_returns(y: Array, x: Array, params, *, cost=0.0):
    """Positions + per-bar net spread returns + hedged-return factor.

    THE semantics-defining PnL of the pairs trade — the sweep, the fused
    kernel's parity contract, and the walk-forward engine all price
    against this one function. Returns ``(pos, net, hr)`` where
    ``hr[t] = (r_y[t] - beta[t-1]*r_x[t]) / max(1 + |beta[t-1]|, 1)`` is
    the gross-normalized spread return of holding one unit into bar t and
    ``net = prev_pos * hr - cost * |Δpos|``. Returns are per unit of gross
    book, so cost is too: leg notional ``|Δpos|*(1+|beta|)`` over the same
    gross normalizer reduces to ``|Δpos|``.
    """
    pos, beta = pairs_positions(y, x, params)
    ry = pnl_mod.simple_returns(y)
    rx = pnl_mod.simple_returns(x)
    prev_pos = jnp.concatenate(
        [jnp.zeros_like(pos[..., :1]), pos[..., :-1]], axis=-1)
    prev_beta = jnp.concatenate(
        [jnp.zeros_like(beta[..., :1]), beta[..., :-1]], axis=-1)
    gross = 1.0 + jnp.abs(prev_beta)
    hr = (ry - prev_beta * rx) / jnp.maximum(gross, 1.0)
    turnover = jnp.abs(pos - prev_pos)
    net = prev_pos * hr - jnp.asarray(cost, y.dtype) * turnover
    return pos, net, hr


def pair_backtest(y: Array, x: Array, params, *, cost=0.0,
                  periods_per_year: int = 252) -> metrics_mod.Metrics:
    """Full backtest of one pair under one param set (vmap target)."""
    pos, net, _ = pair_net_returns(y, x, params, cost=cost)
    equity = 1.0 + jnp.cumsum(net, axis=-1)
    return metrics_mod.summary_metrics(
        net, equity, pos, periods_per_year=periods_per_year)


def _pairs_sweep(y_close: Array, x_close: Array, grid, *, cost,
                 periods_per_year: int) -> metrics_mod.Metrics:
    def per_param(y1, x1, p):
        return pair_backtest(y1, x1, p, cost=cost,
                             periods_per_year=periods_per_year)

    def per_pair(y1, x1):
        return jax.vmap(lambda p: per_param(y1, x1, p))(dict(grid))

    return jax.vmap(per_pair)(y_close, x_close)


@functools.partial(jax.jit, static_argnames=("periods_per_year",))
def run_pairs_sweep(y_close: Array, x_close: Array, grid, *, cost=0.0,
                    periods_per_year: int = 252) -> metrics_mod.Metrics:
    """Evaluate every (pair, param) combo; fields come back ``(n_pairs, P)``.

    ``y_close``/``x_close`` are ``(n_pairs, T)``; ``grid`` maps param name ->
    ``(P,)`` (see :func:`~..parallel.sweep.product_grid`).
    """
    return _pairs_sweep(y_close, x_close, grid, cost=cost,
                        periods_per_year=periods_per_year)


@functools.partial(
    jax.jit, static_argnames=("param_chunk", "periods_per_year"))
def chunked_pairs_sweep(y_close: Array, x_close: Array, grid, *,
                        param_chunk: int, cost=0.0,
                        periods_per_year: int = 252) -> metrics_mod.Metrics:
    """Memory-bounded pairs sweep: ``lax.map`` over param chunks.

    A fully-vmapped pairs sweep materializes ``(pairs, P, T)`` intermediates
    (several live at once — beta, spread, z, positions), which blows past HBM
    at the 1k-pairs x 500-param baseline scale. Chunking the param axis
    bounds live memory exactly like ``sweep.chunked_sweep`` does for the
    single-asset engine. ``P`` must be divisible by ``param_chunk``.
    """
    from ..parallel.sweep import map_param_chunks

    def one_chunk(g):
        return _pairs_sweep(y_close, x_close, g, cost=cost,
                            periods_per_year=periods_per_year)

    return map_param_chunks(grid, param_chunk, one_chunk)
