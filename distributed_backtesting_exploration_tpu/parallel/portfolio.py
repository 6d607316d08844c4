"""Portfolio-level composition of per-ticker backtests (TPU-first).

A parameter sweep answers "which params fit each ticker"; the question a
backtesting framework must answer next is portfolio-level: what do the
selected strategies earn TOGETHER — weighted, netted across the book, with
cross-sectional diagnostics — rather than per ticker in isolation. The
reference never reaches any compute (its worker slot is a sleep stub,
reference ``src/worker/process.rs:21-25``); this module is the aggregation
layer implied by its render-farm framing.

TPU-first design:

- **One jit over the panel.** Per-ticker positions come from the registered
  strategy families ``vmap``-ed over (ticker row, per-ticker param row) —
  the per-ticker parameter selection is data, not Python structure, so one
  compiled program serves any selection.
- **Aggregation is a weighted cross-sectional reduction** per bar (a single
  VPU pass over the ``(N, T)`` net-return panel), and the correlation
  diagnostic is one ``(N, T) x (T, N)`` matmul on the MXU.
- **Cross-chip portfolios ride one `psum`.** With tickers sharded over a
  mesh (`shard_map`), each chip reduces its local book and a single
  ``psum`` over the ticker axis produces the replicated portfolio series —
  the ICI collective IS the portfolio sum (see
  :func:`sharded_portfolio_returns`).

Semantics: portfolio net return per bar is ``sum_i w_i * net_i[t]`` with
``net_i`` each ticker's post-cost strategy return (``ops.pnl
.backtest_prefix``) and ``w`` normalized to sum to 1 — an additive
(non-compounding) book, matching the sweep engine's equity convention.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp

from ..models.base import Strategy
from ..ops import metrics as metrics_mod
from ..ops import pnl as pnl_mod
from . import sweep as sweep_mod

Array = jax.Array


def equal_weights(n: int) -> Array:
    """``(n,)`` weights summing to 1."""
    return jnp.full((n,), 1.0 / float(n), jnp.float32)


def _normalize_weights(weights, n: int) -> Array:
    """Normalize to unit GROSS exposure: ``w / sum(|w|)``.

    Abs-sum (not plain sum) normalization keeps long-short books sane: a
    dollar-neutral ``[1, -1]`` normalizes to ``[0.5, -0.5]`` instead of
    dividing by zero, and a net-short vector keeps its sign instead of
    silently trading inverted. For all-long weights this is the usual
    sum-to-1 normalization.
    """
    if weights is None:
        return equal_weights(n)
    w = jnp.asarray(weights, jnp.float32)
    return w / jnp.maximum(jnp.sum(jnp.abs(w)), 1e-12)


def inverse_vol_weights(close, *, eps: float = 1e-12) -> Array:
    """Full-sample inverse-volatility weights from a ``(N, T)`` close panel.

    ``w_i ∝ 1 / std(simple_returns_i)``, normalized to sum to 1. A
    risk-parity-flavored default that keeps one noisy ticker from owning
    the book; pass custom weights to :func:`portfolio_backtest` for
    anything fancier.
    """
    r = pnl_mod.simple_returns(jnp.asarray(close, jnp.float32))
    inv = 1.0 / (jnp.std(r, axis=-1) + eps)
    return inv / jnp.sum(inv)


def per_ticker_positions(ohlcv, strategy: Strategy,
                         params: Mapping[str, Array]) -> Array:
    """``(N, T)`` positions: each ticker runs ``strategy`` with ITS OWN
    scalar params (``params`` maps field name -> ``(N,)`` array)."""
    return jax.vmap(lambda o, p: strategy.positions(o, p))(
        ohlcv, dict(params))


def portfolio_returns(close, positions, *, weights=None,
                      cost: float = 0.0):
    """Aggregate an ``(N, T)`` book into one portfolio return series.

    Each ticker's post-cost net returns come from
    :func:`~..ops.pnl.backtest_prefix`; the portfolio nets them with
    ``weights`` (normalized to unit gross exposure, see
    :func:`_normalize_weights`; default equal). Returns ``(portfolio_net (T,),
    portfolio_equity (T,), net_exposure (T,))`` — net exposure is the
    weighted sum of per-ticker positions, the book's directional tilt.
    """
    close = jnp.asarray(close, jnp.float32)
    w = _normalize_weights(weights, close.shape[0])
    res = pnl_mod.backtest_prefix(close, positions, cost=cost)
    port_net = jnp.einsum("n,nt->t", w, res.returns)
    port_equity = 1.0 + jnp.cumsum(port_net, axis=-1)
    exposure = jnp.einsum("n,nt->t", w, positions)
    return port_net, port_equity, exposure


def portfolio_backtest(ohlcv, strategy: Strategy,
                       params: Mapping[str, Array], *, weights=None,
                       cost: float = 0.0,
                       periods_per_year: int = 252) -> metrics_mod.Metrics:
    """Scalar :class:`~..ops.metrics.Metrics` for the whole book.

    ``params`` maps each strategy field to an ``(N,)`` per-ticker value —
    typically the output of :func:`select_best_params`. Metrics follow the
    sweep engine's conventions; the ``positions`` feeding
    turnover/n_trades are the book's net exposure.
    """
    pos = per_ticker_positions(ohlcv, strategy, params)
    net, equity, exposure = portfolio_returns(
        ohlcv.close, pos, weights=weights, cost=cost)
    return metrics_mod.summary_metrics(
        net, equity, exposure, periods_per_year=periods_per_year)


def select_best_params(metric_values: Array, grid: Mapping[str, Array], *,
                       metric: str | None = None):
    """Per-ticker argmax over a sweep's ``(N, P)`` metric panel.

    Returns ``(best_values (N,), {field: (N,) best params})`` — the
    direction-aware, NaN-last selection (NaN cells lose to any finite
    cell, matching the worker-side top-k discipline). Delegates to
    :func:`~.sweep.best_params` — ONE selection implementation serves the
    walk-forward refits, the aggregate read path, and this book
    composition. The params dict plugs straight into
    :func:`portfolio_backtest`.
    """
    return sweep_mod.best_params(metric_values, grid, metric=metric)


@functools.partial(
    jax.jit, static_argnames=("strategy", "metric", "periods_per_year"))
def sweep_and_compose(ohlcv, strategy: Strategy, grid: Mapping[str, Array],
                      *, metric: str = "sharpe", weights=None,
                      cost: float = 0.0, periods_per_year: int = 252):
    """End to end: sweep the grid, pick per-ticker winners, price the book.

    Returns ``(portfolio_metrics, chosen_params)``. This is the one-call
    composition path — sweep (vmap over the grid), per-ticker selection,
    and portfolio aggregation all inside ONE jit (strategy/metric are
    static, mirroring ``sweep.jit_sweep``), so the intermediate ``(N, P)``
    matrices never leave the device and the whole composition costs one
    dispatch.
    """
    m = sweep_mod.run_sweep(ohlcv, strategy, grid, cost=cost,
                            periods_per_year=periods_per_year)
    _, chosen = select_best_params(getattr(m, metric), grid, metric=metric)
    pm = portfolio_backtest(ohlcv, strategy, chosen, weights=weights,
                            cost=cost, periods_per_year=periods_per_year)
    return pm, chosen


def correlation_matrix(returns, *, eps: float = 1e-12) -> Array:
    """``(N, N)`` Pearson correlation of an ``(N, T)`` return panel — one
    centered/normalized MXU matmul."""
    r = jnp.asarray(returns, jnp.float32)
    rc = r - jnp.mean(r, axis=-1, keepdims=True)
    norm = jnp.sqrt(jnp.sum(rc * rc, axis=-1, keepdims=True)) + eps
    rn = rc / norm
    return rn @ rn.T


def avg_pairwise_correlation(corr: Array) -> Array:
    """Mean off-diagonal correlation — the book's diversification scalar."""
    n = corr.shape[0]
    off = jnp.sum(corr) - jnp.trace(corr)
    return off / jnp.float32(max(n * (n - 1), 1))


def sharded_portfolio_returns(mesh, close, positions, *, weights=None,
                              cost: float = 0.0, axis: str | None = None):
    """:func:`portfolio_returns` with the ticker axis sharded over ``mesh``.

    Each chip prices its local book slice and reduces it to a weighted
    partial sum; ONE ``psum`` over the mesh axis yields the replicated
    portfolio series — cross-chip composition costs a single collective,
    not a gather of ``(N, T)`` panels. ``N`` must divide evenly by the mesh
    size (pad with zero-weight tickers otherwise). Returns the same
    ``(net, equity, exposure)`` triple, replicated on every chip.
    """
    from jax.sharding import PartitionSpec as P

    close = jnp.asarray(close, jnp.float32)
    n = close.shape[0]
    ax = axis or mesh.axis_names[0]
    n_dev = mesh.shape[ax]
    if n % n_dev:
        raise ValueError(
            f"N={n} tickers not divisible by the {n_dev}-way {ax!r} axis; "
            "pad the book with zero-weight tickers")
    w = _normalize_weights(weights, n)

    def local(close_blk, pos_blk, w_blk):
        res = pnl_mod.backtest_prefix(close_blk, pos_blk, cost=cost)
        part_net = jnp.einsum("n,nt->t", w_blk, res.returns)
        part_exp = jnp.einsum("n,nt->t", w_blk, pos_blk)
        net = jax.lax.psum(part_net, ax)
        exposure = jax.lax.psum(part_exp, ax)
        return net, 1.0 + jnp.cumsum(net, axis=-1), exposure

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ax, None), P(ax, None), P(ax)),
        out_specs=(P(), P(), P()),
    )(close, positions, w)
