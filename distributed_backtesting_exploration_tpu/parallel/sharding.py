"""Multi-chip sweeps: device meshes, sharded data placement, SPMD execution.

The reference's only scale-out axis is job-level data parallelism across
worker *machines* over gRPC (reference ``README.md:6-7``); inside a worker its
intended thread parallelism is stubbed to a serial loop (reference
``src/worker/process.rs:21-25``). Here the intra-worker axis is a TPU slice:
a 1-D ``jax.sharding.Mesh`` over the worker's chips, the ticker axis of a
sweep sharded across it, and the parameter axis dense per chip. A sweep is
embarrassingly parallel over (ticker, param), so the SPMD program needs **no
collectives in the hot loop** — XLA compiles one program per chip and the only
cross-chip traffic is the final ``(tickers, params)`` metric gather (or an
on-device ``psum``-based argmax reduction, :func:`best_over_grid`).

Cross-*host* scale-out stays on the gRPC dispatcher contract over DCN
(``dist/``); this module is the ICI story within one worker.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import metrics as metrics_mod
from ..parallel import sweep as sweep_mod

TICKER_AXIS = "tickers"


def make_mesh(devices=None, *, axis_name: str = TICKER_AXIS) -> Mesh:
    """1-D mesh over the worker's chips (default: all local devices).

    Backtest sweeps shard the ticker axis only, so the mesh is 1-D; the param
    axis stays dense per chip to keep each chip's XLA program a single fused
    (ticker-block x param) kernel.
    """
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def pad_tickers(n_tickers: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` >= ``n_tickers`` (shard-even padding)."""
    return -(-n_tickers // n_shards) * n_shards


def pad_rows(a: np.ndarray, n_pad: int) -> np.ndarray:
    """Pad a row-stacked array to ``n_pad`` rows by repeating the last row.

    THE shard-even padding discipline (used by :func:`device_put_sweep` and
    the worker's mesh dispatch): repeated rows are real, well-formed inputs
    whose outputs callers drop, so no kernel needs a validity mask."""
    a = np.asarray(a)
    n = a.shape[0]
    if n_pad == n:
        return a
    return np.concatenate([a, np.repeat(a[-1:], n_pad - n, axis=0)], axis=0)


def device_put_sweep(mesh: Mesh, ohlcv, grid: Mapping[str, jax.Array],
                     bar_mask=None):
    """Place a sweep's inputs: tickers sharded over the mesh, grid replicated.

    Pads the ticker axis (repeating the last ticker) to a multiple of the mesh
    size so every chip gets an equal block; returns
    ``(ohlcv, grid, bar_mask, n_real)`` with ``n_real`` the unpadded count —
    callers slice results back to ``[:n_real]``.
    """
    axis = mesh.axis_names[0]
    n = ohlcv.close.shape[0]
    n_pad = pad_tickers(n, mesh.devices.size)

    row = NamedSharding(mesh, P(axis, None))
    rep = NamedSharding(mesh, P())
    ohlcv = type(ohlcv)(*(jax.device_put(pad_rows(f, n_pad), row)
                          for f in ohlcv))
    grid = {k: jax.device_put(jnp.asarray(v), rep) for k, v in grid.items()}
    if bar_mask is not None:
        bar_mask = jax.device_put(pad_rows(bar_mask, n_pad), row)
    return ohlcv, grid, bar_mask, n


@functools.partial(
    jax.jit, static_argnames=("mesh", "strategy", "periods_per_year",
                              "param_chunk"))
def sharded_sweep(mesh: Mesh, ohlcv, strategy, grid, *, cost=0.0,
                  bar_mask=None, periods_per_year: int = 252,
                  param_chunk: int | None = None):
    """The multi-chip sweep: ``shard_map`` of the fused kernel over tickers.

    Each chip runs :func:`~.sweep.run_sweep` on its ticker block; outputs are
    ``(n_tickers, P)`` metrics sharded the same way, so nothing but the caller
    ever moves them. Inputs should be placed with :func:`device_put_sweep`.

    ``param_chunk`` composes the two memory valves: the mesh divides the
    ticker axis, the ``lax.map`` chunking bounds the param axis's live
    working set per chip (see :func:`~.sweep.chunked_sweep` — the bound
    survives under ``shard_map`` because ``lax.map`` is sequential).
    """
    axis = mesh.axis_names[0]
    row, rep = P(axis, None), P()
    mask_spec = rep if bar_mask is None else row

    def local(ohlcv_blk, grid_rep, mask_blk):
        if param_chunk:
            return sweep_mod.chunked_sweep(
                ohlcv_blk, strategy, grid_rep, param_chunk=param_chunk,
                cost=cost, bar_mask=mask_blk,
                periods_per_year=periods_per_year)
        return sweep_mod.run_sweep(
            ohlcv_blk, strategy, grid_rep, cost=cost, bar_mask=mask_blk,
            periods_per_year=periods_per_year)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(type(ohlcv)(*(row for _ in ohlcv)),
                  {k: rep for k in grid}, mask_spec),
        out_specs=metrics_mod.Metrics(*(row for _ in metrics_mod.Metrics._fields)),
        check_vma=False)
    return fn(ohlcv, grid, bar_mask)


@functools.partial(
    jax.jit, static_argnames=("mesh", "strategy", "metric", "periods_per_year"))
def best_over_grid(mesh: Mesh, ohlcv, strategy, grid, *, metric: str = "sharpe",
                   cost=0.0, bar_mask=None, periods_per_year: int = 252):
    """Sweep + on-device global argmax over the whole (ticker x param) grid.

    Returns ``(best_value, best_ticker_index, {param: value})`` as scalars —
    the all-reduce pattern for "find the best configuration anywhere in the
    fleet slice" without materializing the full metric matrix on the host.
    The cross-chip reduction is a single ``argmax`` over a gathered per-chip
    maximum (one scalar per chip over ICI).
    """
    axis = mesh.axis_names[0]
    row, rep = P(axis, None), P()
    mask_spec = rep if bar_mask is None else row

    sign = metrics_mod.metric_sign(metric)

    def local(ohlcv_blk, grid_rep, mask_blk):
        m = sweep_mod.run_sweep(
            ohlcv_blk, strategy, grid_rep, cost=cost, bar_mask=mask_blk,
            periods_per_year=periods_per_year)
        vals = sign * getattr(m, metric)               # (tickers/shard, P)
        flat = vals.reshape(-1)
        li = jnp.argmax(flat)
        lv = flat[li]
        # One (value, flat-index) pair per chip crosses ICI.
        all_v = jax.lax.all_gather(lv, axis)           # (n_shards,)
        all_i = jax.lax.all_gather(li, axis)           # (n_shards,)
        shard = jnp.argmax(all_v)
        best_v = all_v[shard]
        n_per = vals.shape[0]
        flat_idx = all_i[shard]
        ticker = shard * n_per + flat_idx // vals.shape[1]
        param = flat_idx % vals.shape[1]
        return best_v, ticker.astype(jnp.int32), param.astype(jnp.int32)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(type(ohlcv)(*(row for _ in ohlcv)),
                  {k: rep for k in grid}, mask_spec),
        out_specs=(rep, rep, rep), check_vma=False)
    best_v, ticker, param = fn(ohlcv, grid, bar_mask)
    chosen = {k: v[param] for k, v in grid.items()}
    return sign * best_v, ticker, chosen
