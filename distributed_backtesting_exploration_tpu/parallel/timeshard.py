"""Time-axis (sequence) parallelism: blockwise scan with ICI carry handoff.

The long-context axis of a backtest is bar time. Indicators are prefix-sum
algebra and the PnL/hysteresis machines are first-order recurrences — the
domain analogue of sequence parallelism is therefore not ring *attention*
(there is no all-pairs interaction) but a **blockwise scan**: shard the time
axis across chips, run the local recurrence per block, then fix up each
block with the carry from the chips to its left. Two primitives cover every
kernel in this framework:

- :func:`sharded_cumsum` — distributed inclusive prefix sum. Local cumsum,
  then one ``psum``-style exclusive scan of per-block totals over ICI
  (implemented with ``all_gather`` of one scalar-per-chip + a masked sum;
  O(T/n) compute, O(n) tiny collective). Rolling sum/mean/var/OLS are all
  cumsum differences, so this makes every indicator time-shardable.
- :func:`sharded_linear_scan` — distributed first-order linear recurrence
  ``y[t] = a[t] * y[t-1] + b[t]`` (EMA, decayed state). Local associative
  scan per block, then a log(n)-step ``ppermute`` ladder combines block
  summaries across chips, and a final local fixup applies each block's
  incoming carry. Exact same math as the single-device
  ``lax.associative_scan`` — verified bit-for-bit in tests.

The band-hysteresis machine — the stateful core of Bollinger/RSI/VWAP/pairs
— time-shards **exactly** as well: its per-bar update is a map on the
3-state space {-1, 0, +1}, and map composition is associative
(``ops.signals.band_transition_maps``), so a block composes into one
3-vector summary, the block summaries fold across chips like the linear
scan's carries, and a local fixup applies each block's incoming state
(:func:`sharded_band_positions`). The Donchian breakout latch is the same
shape of machine, so it shards through the identical fold
(:func:`_transition_positions_local`).

Rolling-extrema state (Donchian channels, stochastic %K) is the fourth
and last state shape: rolling max/min have no cumsum form, but the
reduction never spans more than ``window`` bars, so a bounded halo
(``ppermute`` of the left neighbor's last ``window`` bars) plus a local
sliding ``reduce_window`` reproduces the trailing extrema exactly — no
carry fixup at all (:func:`sharded_donchian_backtest`,
:func:`sharded_stochastic_backtest`).

Only a *general* non-associative state machine (arbitrary
``backtest_scan`` bodies) cannot shard; long histories there use
:func:`chunked_scan` (sequential over chunks, carry threaded on one chip),
which bounds peak memory instead. This mirrors SURVEY.md §5's call:
blockwise scan with carried state, not attention-style ring exchange.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import rolling

TIME_AXIS = "time"


def _exclusive_block_reduce(block_val, axis: str, op, identity):
    """Reduce ``block_val`` with ``op`` over all chips strictly left of this
    one (``identity`` on chip 0).

    ``all_gather`` of one value per chip + masked reduce — O(n_chips)
    scalars over ICI, no host round-trip. The exclusive-prefix pattern
    behind the distributed cumsum (op=sum) and the cross-chip running peak
    (op=max).
    """
    idx = jax.lax.axis_index(axis)
    vals = jax.lax.all_gather(block_val, axis)              # (n, ...)
    n = vals.shape[0]
    mask = (jnp.arange(n) < idx).reshape((n,) + (1,) * block_val.ndim)
    return op(jnp.where(mask, vals, identity), axis=0)


def _exclusive_block_offset(block_total, axis: str):
    """Sum of ``block_total`` over all chips strictly left of this one."""
    return _exclusive_block_reduce(block_total, axis, jnp.sum, 0.0)


def sharded_cumsum(mesh: Mesh, x, *, axis_name: str = TIME_AXIS):
    """Inclusive cumsum along a time axis sharded over ``mesh``.

    ``x`` is ``(..., T)`` with T sharded; result has the same sharding.
    """
    spec = P(*((None,) * (x.ndim - 1) + (axis_name,)))

    def local(x_blk):
        cs = jnp.cumsum(x_blk, axis=-1)
        offset = _exclusive_block_offset(cs[..., -1], axis_name)
        return cs + offset[..., None]

    return jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def sharded_linear_scan(mesh: Mesh, a, b, *, axis_name: str = TIME_AXIS):
    """Distributed ``y[t] = a[t]*y[t-1] + b[t]`` (y[-1] = 0), T sharded.

    Per block, the composition of all steps is itself a first-order map
    ``y_out = A*y_in + B`` with ``A = prod(a)``, ``B`` = the local scan's
    last element. The cross-chip combine gathers one (A, B) pair per chip and
    left-folds the pairs for blocks to this chip's left; each block then
    applies its incoming carry locally: ``y = scan_local + prefix_a * carry_in``
    where ``prefix_a[t] = prod(a[block_start..t])``. At backtest scale the
    n-chip fold of scalars is cheaper than a log-depth ``ppermute`` ladder
    and exact for any mesh size.
    """
    spec = P(*((None,) * (a.ndim - 1) + (axis_name,)))

    def local_simple(a_blk, b_blk):
        return _linear_scan_local(a_blk, b_blk, axis_name)

    return jax.shard_map(local_simple, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(a, b)


def _linear_scan_local(a_blk, b_blk, axis_name: str):
    """Blockwise body of :func:`sharded_linear_scan`, composable inside a
    larger ``shard_map`` (the sharded RSI backtest builds its Wilder EMAs
    with this in the same SPMD program as the band machine)."""
    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    prefix_a, y_local = jax.lax.associative_scan(
        combine, (a_blk, b_blk), axis=-1)
    A = prefix_a[..., -1]
    B = y_local[..., -1]
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    all_A = jax.lax.all_gather(A, axis_name)   # (n, ...)
    all_B = jax.lax.all_gather(B, axis_name)
    # Exclusive left-fold of (A, B) maps for blocks < idx, in order.
    carry = jnp.zeros_like(B)
    for j in range(n):
        take = jnp.asarray(j < idx)
        carry = jnp.where(take, all_A[j] * carry + all_B[j], carry)
    return y_local + prefix_a * carry[..., None]


def _ema_local(x_blk, gidx, alpha, axis_name: str):
    """Blockwise EMA with ``rolling.ema``'s exact seed semantics
    (``y[0] = x[0]``, encoded as ``a[0] = 0, b[0] = x[0]`` at the *global*
    first bar)."""
    t0 = gidx == 0
    a = jnp.where(t0, 0.0, 1.0 - alpha) * jnp.ones_like(x_blk)
    b = jnp.where(t0, x_blk, alpha * x_blk)
    return _linear_scan_local(a, b, axis_name)


def sharded_ema(mesh: Mesh, x, *, span=None, alpha=None,
                axis_name: str = TIME_AXIS):
    """EMA of a ``(..., T)`` series with the TIME axis sharded over ``mesh``.

    Same recurrence and seed as :func:`~..ops.rolling.ema`
    (``y[t] = (1-a)*y[t-1] + a*x[t]``, ``y[0] = x[0]``); the cross-block
    carry is one ``(A, B)`` pair per chip over ICI. An EMA has no window —
    its state is O(1) — so unlike the rolling-window backtests there is no
    halo-fits-one-block constraint: any block size works.
    """
    if (span is None) == (alpha is None):
        raise ValueError("pass exactly one of span= or alpha=")
    if alpha is None:
        alpha = 2.0 / (float(span) + 1.0)
    alpha = jnp.float32(alpha)
    spec = P(*((None,) * (x.ndim - 1) + (axis_name,)))
    n_dev = mesh.shape[axis_name]
    T = x.shape[-1]
    if T % n_dev:
        raise ValueError(
            f"T={T} not divisible by the {n_dev}-way {axis_name!r} axis")

    def local(x_blk):
        Tb = x_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        return _ema_local(x_blk, gidx, alpha, axis_name)

    return jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def chunked_scan(step, init_carry, inputs, *, chunk: int, unroll: int = 8):
    """Memory-bounded sequential scan for non-associative state machines.

    Splits the time axis (leading axis of each leaf of ``inputs``) into
    ``chunk``-sized pieces and runs ``lax.scan`` over chunks of ``lax.scan``
    over bars. Semantically identical to one big scan; peak live activation
    memory drops from O(T) to O(chunk) under ``jax.checkpoint`` of the inner
    scan — the long-history escape hatch for hysteresis strategies.
    """
    leaves = jax.tree_util.tree_leaves(inputs)
    T = leaves[0].shape[0]
    if T % chunk:
        raise ValueError(f"T={T} not divisible by chunk={chunk}")
    n_chunks = T // chunk
    chunked = jax.tree_util.tree_map(
        lambda x: x.reshape((n_chunks, chunk) + x.shape[1:]), inputs)

    @jax.checkpoint
    def run_chunk(carry, xs):
        return jax.lax.scan(step, carry, xs, unroll=unroll)

    carry, ys = jax.lax.scan(run_chunk, init_carry, chunked)
    return carry, jax.tree_util.tree_map(
        lambda y: y.reshape((T,) + y.shape[2:]), ys)


def _from_left(x_blk, k: int, axis_name: str):
    """Last ``k`` elements of the LEFT neighbor's block (zeros on chip 0)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, i + 1) for i in range(n - 1)]
    return jax.lax.ppermute(x_blk[..., -k:], axis_name, perm)


def _pnl_metrics_local(pos, r, gidx, T: int, *, cost: float,
                       periods_per_year: int, axis_name: str,
                       eps: float = 1e-12, prev_pos=None):
    """Blockwise PnL + summary metrics for a time-sharded position path.

    Shared tail of every time-sharded backtest (SMA, Bollinger): one-bar
    position halo for the lagged exposure, net returns locally, then the
    moments / running-peak drawdown / final equity as ``psum``/``pmax``
    reductions with an exclusive cross-chip max for the peak. A caller
    that already exchanged a one-bar halo for its own state (pairs stacks
    beta with pos) passes ``prev_pos`` to keep that single collective.

    ``T`` is the SEMANTIC history length: bars with ``gidx >= T`` (the
    right padding a caller adds to make the panel divisible by the mesh)
    are dead — they contribute zero net return, turnover, and activity,
    the equity curve stays flat through them, and every denominator uses
    ``T``. With repeat-last padding this makes the padded computation
    bit-equal in semantics to the unpadded one (the ``t_real`` contract
    of the ``sharded_*_backtest`` family)."""
    from ..ops.metrics import metrics_from_reductions

    n_f = jnp.float32(T)
    live = gidx < T
    if prev_pos is None:
        prev_pos = jnp.concatenate(
            [_from_left(pos, 1, axis_name), pos[..., :-1]], axis=-1)
    net = prev_pos * r - jnp.float32(cost) * jnp.abs(pos - prev_pos)
    net = jnp.where(live, net, 0.0)

    # Moments / downside via global sums.
    s1 = jax.lax.psum(jnp.sum(net, axis=-1), axis_name)
    s2 = jax.lax.psum(jnp.sum(net * net, axis=-1), axis_name)
    down = jnp.minimum(net, 0.0)
    down_sq = jax.lax.psum(jnp.sum(down * down, axis=-1), axis_name)

    # Equity + running peak across blocks for drawdown.
    eq = 1.0 + jnp.cumsum(net, axis=-1)
    eq = eq + _exclusive_block_offset(net.sum(-1), axis_name)[..., None]
    peak_local = jax.lax.cummax(eq, axis=eq.ndim - 1)
    left_peak = _exclusive_block_reduce(
        jnp.max(eq, axis=-1), axis_name, jnp.max, -jnp.inf)
    peak = jnp.maximum(peak_local, left_peak[..., None])
    dd = (peak - eq) / jnp.maximum(peak, eps)
    mdd = jax.lax.pmax(jnp.max(dd, axis=-1), axis_name)
    eq_final = jax.lax.psum(
        jnp.sum(jnp.where(gidx == T - 1, eq, 0.0), axis=-1), axis_name)

    active = (jnp.abs(prev_pos) > 0) & live
    wins = (net > 0) & active
    wins_sum = jax.lax.psum(
        jnp.sum(wins.astype(jnp.float32), -1), axis_name)
    active_sum = jax.lax.psum(
        jnp.sum(active.astype(jnp.float32), -1), axis_name)
    turnover = jax.lax.psum(
        jnp.sum(jnp.where(live, jnp.abs(pos - prev_pos), 0.0), axis=-1),
        axis_name)
    return metrics_from_reductions(
        s1=s1, s2=s2, downside_sq_sum=down_sq, mdd=mdd,
        eq_final=eq_final, wins_sum=wins_sum, active_sum=active_sum,
        turnover=turnover, n=n_f, periods_per_year=periods_per_year,
        eps=eps)


def _block_returns(close_blk, gidx, axis_name: str):
    """Per-bar simple returns with a one-bar halo (r[0] = 0 globally)."""
    prev_close = jnp.concatenate(
        [_from_left(close_blk, 1, axis_name), close_blk[..., :-1]], axis=-1)
    return jnp.where(gidx == 0, 0.0,
                     close_blk / jnp.where(gidx == 0, 1.0, prev_close) - 1.0)


def _cumsum_ext(series_blk, halo_w: int, axis_name: str, prefix=None):
    """Global compensated prefix sum (``rolling.prefix_sum2``, or the
    local ``(hi, lo)`` pair ``prefix`` gives) of a time-sharded series,
    plus a ``halo_w``-bar left halo — the lagged-read window every
    cumsum-difference rolling sum needs. Returns ``(cs, cs_ext)``, each
    stacking the ``(hi, lo)`` pair on a new leading axis.

    The chips to the left contribute their block totals as pairs, summed
    with ``two_sum`` in chip order, and the offset joins each local pair
    the same way, so the global pair is as exact as the one-device
    ``rolling.rolling_sum``'s and the two agree whatever the block
    boundaries."""
    hi, lo = rolling.prefix_sum2(series_blk) if prefix is None else prefix
    idx = jax.lax.axis_index(axis_name)
    totals = jax.lax.all_gather(jnp.stack([hi[..., -1], lo[..., -1]]),
                                axis_name)                  # (n, 2, ...)
    off_hi = jnp.zeros_like(hi[..., -1])
    off_lo = jnp.zeros_like(off_hi)
    for j in range(totals.shape[0]):
        left = j < idx
        off_hi, err = rolling.two_sum(off_hi,
                                      jnp.where(left, totals[j, 0], 0.0))
        off_lo = off_lo + jnp.where(left, totals[j, 1], 0.0) + err
    hi, err = rolling.two_sum(hi, off_hi[..., None])
    cs = jnp.stack([hi, lo + off_lo[..., None] + err])
    return cs, jnp.concatenate(
        [_from_left(cs, halo_w, axis_name), cs], axis=-1)


def _windowed_sum_blk(cs, cs_ext, gidx, w: int, halo_w: int):
    """Trailing-``w`` rolling sum from the extended prefix pair:
    ``rolling.window_diff`` of ``cs[t]`` and ``cs[t-w]`` with a zero
    lagged read in the global warmup (``t < w``) —
    ``rolling.rolling_sum``'s semantics, blockwise."""
    lagged = _lagged_blk(cs, cs_ext, gidx, w, halo_w)
    return rolling.window_diff(cs[0], cs[1], lagged[0], lagged[1])


def _windowed_sum2_blk(cs, cs_ext, gidx, w: int, halo_w: int):
    """:func:`_windowed_sum_blk` as an unevaluated ``rolling.window_sum2``
    pair (the rolling OLS's double-f32 moments)."""
    lagged = _lagged_blk(cs, cs_ext, gidx, w, halo_w)
    return rolling.window_sum2(cs[0], cs[1], lagged[0], lagged[1])


def _lagged_blk(cs, cs_ext, gidx, w: int, halo_w: int):
    Tb = cs.shape[-1]
    return jnp.where(gidx >= w, jax.lax.slice_in_dim(
        cs_ext, halo_w - w, halo_w - w + Tb, axis=-1), 0.0)


def _windowed_zscore_local(series_blk, gidx, window: int, halo_w: int,
                           T: int, axis_name: str, *, eps: float = 1e-12):
    """Blockwise rolling z-score with series-mean centering — the shared
    signal head of the Bollinger and pairs time-sharded backtests
    (``rolling.rolling_zscore``'s formula: ddof=0, centered second moments
    against the FULL-history mean as the f32 cancellation guard).

    The three windowed sums (centered, centered², raw) ride ONE stacked
    ``_cumsum_ext`` — collectives are latency-bound and XLA will not CSE
    them, so one ``all_gather`` + one ``ppermute`` serve all three (the
    same one-collective discipline as ``_band_positions_local``).
    Per-series numerics are identical to separate calls: the stack axis is
    leading, the scans are per-row.
    """
    w_f = jnp.float32(window)
    # Mean over the LIVE history only (gidx < T): with a right-padded
    # panel the pad bars must not shift the full-history centering.
    mean = (jax.lax.psum(
        jnp.sum(jnp.where(gidx < T, series_blk, 0.0), axis=-1), axis_name)
            / jnp.float32(T))[..., None]
    sc = series_blk - mean
    stacked = jnp.stack([sc, sc * sc, series_blk])
    cs, cs_ext = _cumsum_ext(stacked, halo_w, axis_name)
    s = _windowed_sum_blk(cs, cs_ext, gidx, window, halo_w)
    s1, s2, ssum = s[0], s[1], s[2]
    var = jnp.maximum((s2 - s1 * s1 / w_f) / w_f, 0.0)
    return (series_blk - ssum / w_f) / (jnp.sqrt(var) + eps)


def _transition_positions_local(maps, axis_name: str):
    """Position path of ANY {-1,0,+1} transition-map machine, one time
    block, exact across blocks.

    The block's prefix maps come from a local shift-doubling prefix
    composition, the whole block composes into one 3-vector summary, and
    the state
    *entering* this block is the exclusive left-fold of block summaries
    over ICI (same carry pattern as :func:`sharded_linear_scan` — one
    3-vector per chip crosses the wire). The fixup routes each bar's
    prefix map through the incoming state. Shared by the band-hysteresis
    machine (Bollinger/RSI/pairs/stochastic) and the Donchian breakout
    latch — any stateful strategy whose per-bar update is a map on the
    3-state space shards through here."""
    from ..ops import signals

    # Shift-doubling ladder, not associative_scan: bit-identical for
    # select-only map composition and avoids the scan lowering's
    # load-sensitive native compile (signals.prefix_compose_maps).
    pm, p0, pp = signals.prefix_compose_maps(maps)

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    # One latency-bound collective, not three: the block summary is a
    # stacked (3, ...) map — (next state from -1, from 0, from +1).
    summary = jnp.stack([pm[..., -1], p0[..., -1], pp[..., -1]])
    alls = jax.lax.all_gather(summary, axis_name)           # (n, 3, ...)
    # Exclusive left-fold: start flat, apply each earlier block's map.
    state = jnp.zeros_like(p0[..., -1])
    for j in range(n):
        nxt = jnp.where(state < 0, alls[j, 0],
                        jnp.where(state > 0, alls[j, 2], alls[j, 1]))
        state = jnp.where(j < idx, nxt, state)
    state = state[..., None]
    return jnp.where(state < 0, pm, jnp.where(state > 0, pp, p0))


def _band_positions_local(z_blk, valid_blk, z_entry, z_exit, axis_name: str):
    """Band-hysteresis positions for one time block, exact across blocks
    (``ops.signals.band_transition_maps`` composed through
    :func:`_transition_positions_local`)."""
    from ..ops import signals

    maps = signals.band_transition_maps(z_blk, valid_blk, z_entry, z_exit)
    return _transition_positions_local(maps, axis_name)


def _latch_maps(up, down, valid):
    """Per-bar transition maps of the Donchian breakout latch
    (``models.donchian._latch``'s step): break above the prior channel
    high -> +1 from any state, below the prior low -> -1, else hold;
    invalid bars force flat. ``up`` wins over ``down`` (a bar clearing
    both channels goes long), exactly as the scan's nested ``where``."""
    def nxt_from(prev):
        return jnp.where(up, 1.0, jnp.where(down, -1.0, prev))

    one = jnp.ones(up.shape, jnp.float32)
    zero = jnp.zeros_like(one)
    v = jnp.broadcast_to(valid, up.shape)
    return (jnp.where(v, nxt_from(-one), zero),
            jnp.where(v, nxt_from(zero), zero),
            jnp.where(v, nxt_from(one), zero))


def _reduce_window_last(x, w: int, mode: str):
    """Sliding extrema over the last axis: ``out[..., j] = mode(x[..., j:j+w])``
    (VALID — output length ``x.shape[-1] - w + 1``)."""
    init = -jnp.inf if mode == "max" else jnp.inf
    comp = jax.lax.max if mode == "max" else jax.lax.min
    dims = (1,) * (x.ndim - 1) + (w,)
    return jax.lax.reduce_window(x, init, comp, dims, (1,) * x.ndim, "VALID")


def sharded_band_positions(mesh: Mesh, z, valid, z_entry, z_exit=0.0, *,
                           axis_name: str = TIME_AXIS):
    """Band-hysteresis position path with the TIME axis sharded.

    Exact (bit-level) match to ``ops.signals.band_hysteresis_assoc`` on the
    unsharded inputs: states are small integers in float32 and every
    comparison sees the same values, so sharding changes nothing but where
    the composition happens. ``z``/``valid`` are ``(..., T)`` with T
    sharded over ``mesh``'s ``axis_name``; ``z_entry``/``z_exit`` are
    scalars (replicated)."""
    spec = P(*((None,) * (z.ndim - 1) + (axis_name,)))

    def local(z_blk, valid_blk):
        return _band_positions_local(z_blk, valid_blk, z_entry, z_exit,
                                     axis_name)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(
        z, jnp.broadcast_to(valid, z.shape))


def sharded_sma_backtest(mesh: Mesh, close, fast: int, slow: int, *,
                         cost: float = 0.0, periods_per_year: int = 252,
                         axis_name: str = TIME_AXIS,
                         t_real: int | None = None):
    """End-to-end SMA-crossover backtest with the TIME axis sharded.

    The composed long-context path: for a ``(..., T)`` close panel whose
    bar axis is sharded across ``mesh``, every stage runs blockwise with
    O(1)-per-chip ICI traffic — returns via a one-bar halo exchange
    (``ppermute``), rolling SMAs via the distributed cumsum plus a
    ``max(fast, slow)``-bar halo for the lagged prefix, PnL locally, and
    the summary metrics as ``psum``/``pmax`` reductions (the running-peak
    drawdown uses an exclusive cross-chip max of block maxima). One
    history longer than any single chip's memory therefore backtests
    without ever materializing the full series in one place.

    ``fast``/``slow`` are static ints with ``slow <= block length`` (the
    halo must fit one neighbor block). Returns
    :class:`~..ops.metrics.Metrics` with scalar-per-series fields,
    replicated across the mesh. Matches the unsharded
    single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    if not (0 < fast < slow):
        raise ValueError(f"need 0 < fast < slow, got {fast}, {slow}")
    n_dev = mesh.shape[axis_name]   # the TIME axis size, not total devices
    T_pad = close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if slow > T_pad // n_dev:
        raise ValueError(
            f"slow={slow} exceeds the {T_pad // n_dev}-bar block; the halo "
            "exchange needs the window to fit one neighbor block")
    T = _resolve_t_real(T_pad, t_real)
    halo_w = slow
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))   # metrics drop the time axis

    def local(close_blk):
        Tb = close_blk.shape[-1]
        idx = jax.lax.axis_index(axis_name)
        gidx = jnp.arange(Tb) + idx * Tb                  # global bar index
        r = _block_returns(close_blk, gidx, axis_name)

        # Global prefix sum of closes; lagged reads via a slow-bar halo.
        cs, cs_ext = _cumsum_ext(close_blk, halo_w, axis_name)

        def sma(w):
            return _windowed_sum_blk(cs, cs_ext, gidx, w,
                                     halo_w) / jnp.float32(w)

        valid = gidx >= slow - 1
        pos = jnp.where(valid, jnp.sign(sma(fast) - sma(slow)), 0.0)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_bollinger_backtest(mesh: Mesh, close, window: int, k: float, *,
                               z_exit: float = 0.0, cost: float = 0.0,
                               periods_per_year: int = 252,
                               axis_name: str = TIME_AXIS,
                               t_real: int | None = None):
    """End-to-end Bollinger mean-reversion backtest, TIME axis sharded.

    The long-context composition for a *stateful* strategy: blockwise
    rolling z-score (distributed cumsums of the series-centered moments +
    a ``window``-bar halo, ``rolling.rolling_zscore``'s formula) feeding
    the exactly-sharded band machine (:func:`_band_positions_local`) and
    the shared blockwise PnL/metrics tail. One history longer than any
    single chip's memory runs the full hysteresis strategy without ever
    materializing the series in one place — the reference has no analogue
    (its compute slot is a sleep stub, reference
    ``src/worker/process.rs:21-25``).

    ``window`` is a static int with ``window <= block length`` (halo
    bound). Returns scalar-per-series :class:`~..ops.metrics.Metrics`,
    replicated. Matches the single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if window > T_pad // n_dev:
        raise ValueError(
            f"window={window} exceeds the {T_pad // n_dev}-bar block; the "
            "halo exchange needs the window to fit one neighbor block")
    T = _resolve_t_real(T_pad, t_real)
    halo_w = window
    eps = 1e-12
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        idx = jax.lax.axis_index(axis_name)
        gidx = jnp.arange(Tb) + idx * Tb
        r = _block_returns(close_blk, gidx, axis_name)

        z = _windowed_zscore_local(close_blk, gidx, window, halo_w, T,
                                   axis_name, eps=eps)
        valid = gidx >= window - 1
        z = jnp.where(valid, z, 0.0)

        pos = _band_positions_local(z, jnp.broadcast_to(valid, z.shape),
                                    jnp.float32(k), jnp.float32(z_exit),
                                    axis_name)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_rsi_backtest(mesh: Mesh, close, period: int, band: float, *,
                         cost: float = 0.0, periods_per_year: int = 252,
                         axis_name: str = TIME_AXIS,
                         t_real: int | None = None):
    """End-to-end RSI mean-reversion backtest, TIME axis sharded.

    The *EMA-state* long-context composition (Bollinger covers the
    rolling-window case): Wilder's smoothed gain/loss averages are
    first-order linear recurrences, so each runs blockwise through
    :func:`_linear_scan_local` with one ``(A, B)`` carry pair per chip over
    ICI — no halo at all, since an EMA's state is O(1) rather than a
    window of bars. The resulting centered RSI feeds the exactly-sharded
    band machine (:func:`_band_positions_local`, ``models.rsi`` semantics:
    long below ``50 - band``, short above ``50 + band``, exit at 50) and
    the shared blockwise PnL/metrics tail. Only the one-bar return/diff
    halo constrains the block size.

    ``period`` is a static int (the per-chip sweep path vmaps over traced
    periods; this is the one-long-history path). Returns scalar-per-series
    :class:`~..ops.metrics.Metrics`, replicated. Matches the unsharded
    ``rsi`` strategy backtest to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if period < 1:
        raise ValueError(f"period must be >= 1, got {period}")
    T = _resolve_t_real(T_pad, t_real)
    alpha = jnp.float32(1.0 / period)   # Wilder's decay (models.rsi)
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        idx = jax.lax.axis_index(axis_name)
        gidx = jnp.arange(Tb) + idx * Tb

        # ONE one-bar halo exchange serves both the returns and the RSI
        # diff (collectives are latency-bound; XLA is not guaranteed to
        # CSE two identical ppermutes).
        prev = jnp.concatenate(
            [_from_left(close_blk, 1, axis_name), close_blk[..., :-1]],
            axis=-1)
        r = jnp.where(gidx == 0, 0.0,
                      close_blk / jnp.where(gidx == 0, 1.0, prev) - 1.0)
        # diff[0] = 0 globally (jnp.diff prepend=x0 semantics).
        diff = jnp.where(gidx == 0, 0.0, close_blk - prev)
        avg_gain = _ema_local(jnp.maximum(diff, 0.0), gidx, alpha, axis_name)
        avg_loss = _ema_local(jnp.maximum(-diff, 0.0), gidx, alpha,
                              axis_name)
        rsi = 100.0 - 100.0 / (1.0 + avg_gain / (avg_loss + 1e-12))

        valid = gidx >= period   # rolling.valid_mask(T, period + 1)
        pos = _band_positions_local(
            rsi - 50.0, jnp.broadcast_to(valid, rsi.shape),
            jnp.float32(band), jnp.float32(0.0), axis_name)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_pairs_backtest(mesh: Mesh, y_close, x_close, lookback: int,
                           z_entry: float, *, z_exit: float = 0.0,
                           cost: float = 0.0, periods_per_year: int = 252,
                           axis_name: str = TIME_AXIS,
                           t_real: int | None = None):
    """End-to-end rolling-OLS pairs backtest, TIME axis sharded.

    The two-legged long-context composition — every blockwise piece this
    module already has, assembled for the hardest single-pair strategy:
    distributed cumsums of the centered OLS moments (``lookback``-bar
    halos) give the rolling hedge ratio, the spread z-scores reuse the
    same windowed-sum primitive, the exactly-sharded band machine turns z
    into positions, and the shared PnL tail prices the *hedged* spread
    return ``(r_y - beta[t-1] r_x) / max(1 + |beta[t-1]|, 1)`` — the tail
    takes any per-bar return factor, so pairs need no new reduction code.
    Formulas mirror ``models.pairs.pair_backtest`` (series-centered
    moments, eps=1e-12, warmup spread = y, valid from ``2*lookback - 1``
    bars). Parity with the single-device computation is f32-tight except
    at knife-edge band entries: the blockwise cumsum rounds z ~1e-6
    differently, and a bar where ``|z - z_entry|`` is that small can
    resolve differently, moving a long history's metrics by ~1e-3
    relative per flipped bar (the same caveat class as the fused pairs
    kernel; the parity test bounds both the flip count and the
    non-flipped error).

    ``lookback`` is a static int with ``lookback <= block length`` (halo
    bound). Returns scalar-per-pair :class:`~..ops.metrics.Metrics`,
    replicated.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = y_close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if lookback > T_pad // n_dev:
        raise ValueError(
            f"lookback={lookback} exceeds the {T_pad // n_dev}-bar block; "
            "the halo exchange needs the window to fit one neighbor block")
    T = _resolve_t_real(T_pad, t_real)
    halo_w = lookback
    eps = 1e-12
    w_f = jnp.float32(lookback)
    spec = P(*((None,) * (y_close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (y_close.ndim - 1)))

    def local(y_blk, x_blk):
        Tb = y_blk.shape[-1]
        idx = jax.lax.axis_index(axis_name)
        gidx = jnp.arange(Tb) + idx * Tb
        # Both legs' returns through ONE one-bar halo exchange.
        r2 = _block_returns(jnp.stack([y_blk, x_blk]), gidx, axis_name)
        ry, rx = r2[0], r2[1]

        # Series means over the LIVE history (psum, gidx < T so right
        # padding can't shift them), the same f32 cancellation guard as
        # rolling.rolling_ols.
        my = (jax.lax.psum(
            jnp.sum(jnp.where(gidx < T, y_blk, 0.0), axis=-1), axis_name)
              / jnp.float32(T))[..., None]
        mx = (jax.lax.psum(
            jnp.sum(jnp.where(gidx < T, x_blk, 0.0), axis=-1), axis_name)
              / jnp.float32(T))[..., None]
        yc, xc = y_blk - my, x_blk - mx

        # All four OLS moment sums through ONE stacked _cumsum_ext
        # (collectives are latency-bound; one all_gather + one ppermute
        # serve the stack — same discipline as _windowed_zscore_local).
        # rolling.rolling_ols's double-f32 moments: exact products, and
        # window sums kept as pairs into rolling.ols_slope2.
        pfx = [rolling.prefix_sum2(xc), rolling.prefix_sum2(yc),
               rolling.prefix_prod2(xc, xc), rolling.prefix_prod2(xc, yc)]
        cs, cs_ext = _cumsum_ext(
            None, halo_w, axis_name,
            prefix=tuple(jnp.stack([p[k] for p in pfx]) for k in (0, 1)))
        s, e = _windowed_sum2_blk(cs, cs_ext, gidx, lookback, halo_w)
        sx, sy, sxx, sxy = ((s[i], e[i]) for i in range(4))
        beta = rolling.ols_slope2(sx, sy, sxx, sxy, w_f, eps)
        sx, sy = sx[0] + sx[1], sy[0] + sy[1]
        ok_w = gidx >= lookback - 1
        # The residual on the centred legs (rolling.rolling_ols_residual).
        # Warmup spread is exactly y (rolling_ols fill=0.0): those bars
        # feed the z-score's series mean and early windowed sums.
        spread = jnp.where(ok_w, (yc - sy / w_f) - beta * (xc - sx / w_f),
                           y_blk)
        beta = jnp.where(ok_w, beta, 0.0)

        z = _windowed_zscore_local(spread, gidx, lookback, halo_w, T,
                                   axis_name, eps=eps)
        valid = gidx >= 2 * lookback - 2
        z = jnp.where(valid, z, 0.0)

        pos = _band_positions_local(z, jnp.broadcast_to(valid, z.shape),
                                    jnp.float32(z_entry),
                                    jnp.float32(z_exit), axis_name)
        # ONE one-bar halo exchange serves both lagged states (pos for the
        # PnL tail, beta for the hedge) — same discipline as the returns.
        pb = jnp.stack([pos, beta])
        prev = jnp.concatenate(
            [_from_left(pb, 1, axis_name), pb[..., :-1]], axis=-1)
        prev_pos, prev_beta = prev[0], prev[1]
        gross = 1.0 + jnp.abs(prev_beta)
        hr = (ry - prev_beta * rx) / jnp.maximum(gross, 1.0)
        return _pnl_metrics_local(pos, hr, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name, prev_pos=prev_pos)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=out_specs, check_vma=False)(
        y_close, x_close)


def _resolve_t_real(T_pad: int, t_real) -> int:
    """Semantic history length of a right-padded panel.

    The ``t_real`` contract shared by every ``sharded_*_backtest``: a
    caller whose history is not divisible by the mesh pads the time axis
    on the RIGHT with repeat-last values up to ``T_pad`` and passes the
    real length here. Pad bars then earn zero return, zero turnover, and
    zero weight in every mean/metric denominator (see
    :func:`_pnl_metrics_local`), so the padded result equals the
    unpadded one exactly — the same discipline as the fused kernels'
    per-ticker ``t_real`` (``ops.fused``)."""
    if t_real is None:
        return T_pad
    t = int(t_real)
    if not 0 < t <= T_pad:
        raise ValueError(
            f"t_real={t} must be in (0, {T_pad}] (the padded length)")
    return t


def _check_time_axis(T: int, n_dev: int, window: int, axis_name: str,
                     what: str):
    if window < 1:
        # A non-positive window would not crash: the windowed sums divide
        # by w and the halo slice x[..., -0:] takes the FULL block, so the
        # call would return silent NaN/garbage metrics instead of failing.
        raise ValueError(f"{what} must be >= 1, got {window}")
    if T % n_dev:
        raise ValueError(
            f"T={T} not divisible by the {n_dev}-way {axis_name!r} axis")
    if window > T // n_dev:
        raise ValueError(
            f"{what}={window} exceeds the {T // n_dev}-bar block; the halo "
            "exchange needs the window to fit one neighbor block")


def _donchian_metrics_local(latch_src, hi_src, lo_src, gidx, window: int,
                            T: int, *, cost: float, periods_per_year: int,
                            axis_name: str):
    """Shared blockwise body of both Donchian variants: ONE stacked
    ``window``-bar halo exchange serves the returns' lagged close and both
    prior-channel extrema (collectives are latency-bound — same
    one-collective discipline as the z-score/pairs paths). The prior
    channel at bar t reduces bars ``t-window .. t-1``; the breakout latch
    is a 3-state transition-map machine, so it composes across chips
    exactly like the band machine."""
    w = window
    stacked = (latch_src if hi_src is latch_src
               else jnp.stack([latch_src, hi_src, lo_src]))
    ext = jnp.concatenate([_from_left(stacked, w, axis_name), stacked],
                          axis=-1)
    if hi_src is latch_src:
        close_ext, hi_ext, lo_ext = ext, ext, ext
        close_blk = latch_src
    else:
        close_ext, hi_ext, lo_ext = ext[0], ext[1], ext[2]
        close_blk = latch_src
    Tb = close_blk.shape[-1]

    prev_close = jax.lax.slice_in_dim(close_ext, w - 1, w - 1 + Tb, axis=-1)
    r = jnp.where(gidx == 0, 0.0,
                  close_blk / jnp.where(gidx == 0, 1.0, prev_close) - 1.0)

    # hi_prev[t] = max(src[t-w .. t-1]): the w-window starting at local i
    # of the w-halo'd series. Warmup values are garbage on chip 0 (zero
    # halo) — masked by `valid` below, exactly like the unsharded fill.
    hi_prev = jax.lax.slice_in_dim(
        _reduce_window_last(hi_ext, w, "max"), 0, Tb, axis=-1)
    lo_prev = jax.lax.slice_in_dim(
        _reduce_window_last(lo_ext, w, "min"), 0, Tb, axis=-1)

    valid = gidx >= w            # rolling.valid_mask(T, w + 1)
    up = close_blk >= hi_prev
    down = close_blk <= lo_prev
    pos = _transition_positions_local(_latch_maps(up, down, valid),
                                      axis_name)
    return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                              periods_per_year=periods_per_year,
                              axis_name=axis_name)


def sharded_donchian_backtest(mesh: Mesh, close, window: int, *,
                              cost: float = 0.0, periods_per_year: int = 252,
                              axis_name: str = TIME_AXIS,
                              t_real: int | None = None):
    """End-to-end Donchian-channel breakout backtest, TIME axis sharded.

    The *rolling-extrema-state* long-context composition — the fourth and
    last state shape (after windowed-sum, EMA, and band-machine states):
    rolling max/min have no cumsum form, so the channel extrema come from
    a bounded halo instead of a distributed prefix sum — each bar's
    ``window``-bar channel reaches at most ``window`` bars into the left
    neighbor's block, so ONE stacked ``ppermute`` plus a local sliding
    ``reduce_window`` reproduces the trailing extrema exactly (extrema
    need no carry fixup at all: unlike a cumsum the reduction never spans
    more than ``window`` bars). The breakout latch (hold until the
    opposite channel is touched) is a {-1,0,+1} transition-map machine —
    ``models.donchian._latch``'s scan — so it composes across chips
    through the same 3-vector summary fold as the band machine
    (:func:`_transition_positions_local`). Semantics match
    ``models.donchian`` (channel at bar t uses bars ``t-window..t-1``,
    ties break long, warmup flat, valid from ``window`` bars).

    ``window`` is a static int with ``window <= block length`` (halo
    bound). Returns scalar-per-series :class:`~..ops.metrics.Metrics`,
    replicated. Matches the single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, window, axis_name, "window")
    T = _resolve_t_real(T_pad, t_real)
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        return _donchian_metrics_local(
            close_blk, close_blk, close_blk, gidx, window, T, cost=cost,
            periods_per_year=periods_per_year, axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_donchian_hl_backtest(mesh: Mesh, close, high, low, window: int,
                                 *, cost: float = 0.0,
                                 periods_per_year: int = 252,
                                 axis_name: str = TIME_AXIS,
                                 t_real: int | None = None):
    """Classic high/low-channel Donchian breakout, TIME axis sharded.

    Same composition as :func:`sharded_donchian_backtest` with the
    channels built from the HIGH/LOW columns (``models.donchian``'s
    ``donchian_hl``); the three series share ONE stacked halo exchange.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, window, axis_name, "window")
    T = _resolve_t_real(T_pad, t_real)
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk, high_blk, low_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        return _donchian_metrics_local(
            close_blk, high_blk, low_blk, gidx, window, T, cost=cost,
            periods_per_year=periods_per_year, axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=out_specs, check_vma=False)(
        close, high, low)


def sharded_stochastic_backtest(mesh: Mesh, close, high, low, window: int,
                                band: float, *, cost: float = 0.0,
                                periods_per_year: int = 252,
                                axis_name: str = TIME_AXIS,
                                t_real: int | None = None):
    """End-to-end stochastic-%K mean-reversion backtest, TIME axis sharded.

    Rolling-extrema state feeding the band machine: the trailing
    ``window``-bar high/low channel comes from the bounded-halo sliding
    ``reduce_window`` (window ends AT bar t here — lag 0, vs the Donchian
    channel's lag 1), %K centers it, and the exactly-sharded band machine
    plus the shared PnL tail finish the composition. Semantics match
    ``models.stochastic`` (flat channel -> neutral 50, valid from
    ``window - 1`` bars, enter long below ``50 - band``, exit at 50).

    ``window`` is a static int with ``window <= block length``. Returns
    scalar-per-series :class:`~..ops.metrics.Metrics`, replicated.
    """
    from ..ops.metrics import Metrics

    eps = 1e-12
    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, window, axis_name, "window")
    T = _resolve_t_real(T_pad, t_real)
    halo = max(window - 1, 1)    # extrema need w-1 left bars; returns need 1
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk, high_blk, low_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb

        # ONE stacked halo exchange serves the lagged close and both
        # channel extrema.
        stacked = jnp.stack([close_blk, high_blk, low_blk])
        ext = jnp.concatenate([_from_left(stacked, halo, axis_name),
                               stacked], axis=-1)
        prev_close = jax.lax.slice_in_dim(ext[0], halo - 1, halo - 1 + Tb,
                                          axis=-1)
        r = jnp.where(gidx == 0, 0.0,
                      close_blk / jnp.where(gidx == 0, 1.0, prev_close)
                      - 1.0)

        # hh[t] = max(high[t-w+1 .. t]): w-window ending at local i, i.e.
        # starting at ext index i + halo - w + 1.
        start = halo - window + 1
        hh = jax.lax.slice_in_dim(
            _reduce_window_last(ext[1], window, "max"), start, start + Tb,
            axis=-1)
        ll = jax.lax.slice_in_dim(
            _reduce_window_last(ext[2], window, "min"), start, start + Tb,
            axis=-1)
        rng = hh - ll
        k_pct = jnp.where(rng > eps, 100.0 * (close_blk - ll) / (rng + eps),
                          50.0)

        valid = gidx >= window - 1   # rolling.valid_mask(T, window)
        pos = _band_positions_local(
            jnp.where(valid, k_pct - 50.0, 0.0),
            jnp.broadcast_to(valid, k_pct.shape), jnp.float32(band),
            jnp.float32(0.0), axis_name)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=out_specs, check_vma=False)(
        close, high, low)


def sharded_trix_backtest(mesh: Mesh, close, span: int, signal: int, *,
                          cost: float = 0.0, periods_per_year: int = 252,
                          axis_name: str = TIME_AXIS,
                          t_real: int | None = None):
    """End-to-end TRIX signal-line backtest, TIME axis sharded.

    Pure EMA-state composition (``models.trix`` semantics): the triple
    smoothing is three chained blockwise linear scans
    (:func:`_ema_local` — one ``(A, B)`` carry pair per chip each, no
    halo), the one-bar rate of change reuses the return halo exchange,
    and the signal line is a fourth blockwise EMA over the trix series.
    Like the sharded RSI path, only the one-bar halo constrains the block
    size — EMA state is O(1), so histories of any length shard.

    ``span``/``signal`` are static ints. Returns scalar-per-series
    :class:`~..ops.metrics.Metrics`, replicated. Matches the unsharded
    ``trix`` strategy backtest to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if span < 1 or signal < 1:
        raise ValueError(f"spans must be >= 1, got {span}, {signal}")
    T = _resolve_t_real(T_pad, t_real)
    a_span = jnp.float32(2.0 / (span + 1.0))
    a_sig = jnp.float32(2.0 / (signal + 1.0))
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        r = _block_returns(close_blk, gidx, axis_name)

        e3 = _ema_local(
            _ema_local(
                _ema_local(close_blk, gidx, a_span, axis_name),
                gidx, a_span, axis_name),
            gidx, a_span, axis_name)
        # One-bar rate of change: trix[0] = 0 globally (models.trix seeds
        # the lagged read with e3[0]).
        e3_prev = jnp.concatenate(
            [_from_left(e3, 1, axis_name), e3[..., :-1]], axis=-1)
        trix = jnp.where(gidx == 0, 0.0,
                         e3 / jnp.where(gidx == 0, 1.0, e3_prev) - 1.0)
        sig = _ema_local(trix, gidx, a_sig, axis_name)

        warm = 3 * span + signal - 2
        valid = gidx >= warm - 1   # rolling.valid_mask(T, warm)
        pos = jnp.where(valid, jnp.sign(trix - sig), 0.0)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_momentum_backtest(mesh: Mesh, close, lookback: int, *,
                              cost: float = 0.0, periods_per_year: int = 252,
                              axis_name: str = TIME_AXIS,
                              t_real: int | None = None):
    """End-to-end time-series momentum backtest, TIME axis sharded.

    The simplest windowed composition (``models.momentum`` semantics:
    ``sign(close[t] - close[t-lookback])``, valid from ``lookback`` bars):
    the lagged read is a pure bounded-halo exchange — no cumsum, no carry —
    so ONE stacked ``ppermute`` of the left neighbor's last ``lookback``
    bars serves both the one-bar return lag and the momentum lag.

    ``lookback`` is a static int with ``lookback <= block length`` (halo
    bound). Returns scalar-per-series :class:`~..ops.metrics.Metrics`,
    replicated. Matches the single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, lookback, axis_name, "lookback")
    T = _resolve_t_real(T_pad, t_real)
    halo = lookback
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        ext = jnp.concatenate([_from_left(close_blk, halo, axis_name),
                               close_blk], axis=-1)
        prev_close = jax.lax.slice_in_dim(ext, halo - 1, halo - 1 + Tb,
                                          axis=-1)
        r = jnp.where(gidx == 0, 0.0,
                      close_blk / jnp.where(gidx == 0, 1.0, prev_close)
                      - 1.0)
        # past[t] = close[t - lookback]; chip 0's zero halo is garbage in
        # the warmup region, masked by `valid` exactly like the unsharded
        # clipped-gather fill.
        past = jax.lax.slice_in_dim(ext, 0, Tb, axis=-1)
        valid = gidx >= lookback      # rolling.valid_mask(T, lookback + 1)
        pos = jnp.where(valid, jnp.sign(close_blk - past), 0.0)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_bollinger_touch_backtest(mesh: Mesh, close, window: int,
                                     k: float, *, cost: float = 0.0,
                                     periods_per_year: int = 252,
                                     axis_name: str = TIME_AXIS,
                                     t_real: int | None = None):
    """Path-free Bollinger band-touch backtest, TIME axis sharded.

    Same blockwise rolling z-score as :func:`sharded_bollinger_backtest`
    (distributed centered cumsums + ``window``-bar halo), but the exposure
    is memoryless — ``+1`` below the lower band, ``-1`` above the upper,
    flat inside (``models.bollinger._touch_positions``) — so no state
    machine composes across chips at all: the position is a local map of
    the z block.

    ``window`` is a static int with ``window <= block length``. Returns
    scalar-per-series :class:`~..ops.metrics.Metrics`, replicated.
    Matches the single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, window, axis_name, "window")
    T = _resolve_t_real(T_pad, t_real)
    halo_w = window
    eps = 1e-12
    k_f = jnp.float32(k)
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        r = _block_returns(close_blk, gidx, axis_name)
        z = _windowed_zscore_local(close_blk, gidx, window, halo_w, T,
                                   axis_name, eps=eps)
        valid = gidx >= window - 1
        z = jnp.where(valid, z, 0.0)
        pos = jnp.where(z < -k_f, 1.0, jnp.where(z > k_f, -1.0, 0.0))
        pos = jnp.where(valid, pos, 0.0)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_keltner_backtest(mesh: Mesh, close, high, low, window: int,
                             k: float, *, cost: float = 0.0,
                             periods_per_year: int = 252,
                             axis_name: str = TIME_AXIS,
                             t_real: int | None = None):
    """End-to-end Keltner-channel mean-reversion backtest, TIME axis sharded.

    A *mixed-state* composition (``models.keltner`` semantics): the EMA
    midline is a blockwise linear scan (one ``(A, B)`` carry pair per
    chip), the ATR is a windowed mean of the true range (distributed
    cumsum + ``window``-bar halo), and the ATR-normalized deviation feeds
    the exactly-sharded band machine. The true range's lagged close rides
    a one-bar halo (first global bar uses ``high - low`` via a
    ``close``-valued pad, matching the unsharded ``true_range``).

    ``window`` is a static int with ``window <= block length``. Returns
    scalar-per-series :class:`~..ops.metrics.Metrics`, replicated.
    Matches the single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, window, axis_name, "window")
    T = _resolve_t_real(T_pad, t_real)
    alpha = jnp.float32(2.0 / (window + 1.0))
    eps = 1e-12
    k_f = jnp.float32(k)
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk, high_blk, low_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        # ONE one-bar halo exchange serves the returns and the true
        # range's lagged close (the sharded-RSI discipline).
        prev_raw = jnp.concatenate(
            [_from_left(close_blk, 1, axis_name), close_blk[..., :-1]],
            axis=-1)
        r = jnp.where(gidx == 0, 0.0,
                      close_blk / jnp.where(gidx == 0, 1.0, prev_raw) - 1.0)
        # models.keltner.true_range pads the first bar's lagged close with
        # close[0] itself (|high - close[0]| etc. still <= high - low
        # bounds the max correctly only when close[0] is inside the bar —
        # we reproduce the reference formula, not a re-derivation).
        prev_c = jnp.where(gidx == 0, close_blk, prev_raw)
        tr = jnp.maximum(high_blk - low_blk,
                         jnp.maximum(jnp.abs(high_blk - prev_c),
                                     jnp.abs(low_blk - prev_c)))
        mid = _ema_local(close_blk, gidx, alpha, axis_name)
        cs, cs_ext = _cumsum_ext(tr, window, axis_name)
        atr = _windowed_sum_blk(cs, cs_ext, gidx, window,
                                window) / jnp.float32(window)
        dev = close_blk - mid
        valid = gidx >= window - 1    # rolling.valid_mask(T, window)
        # keltner_z: zero-ATR (or warmup-NaN in the unsharded path) -> 0.
        z = jnp.where(valid & (atr > eps), dev / (atr + eps), 0.0)
        pos = _band_positions_local(z, jnp.broadcast_to(valid, z.shape),
                                    k_f, jnp.float32(0.0), axis_name)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=out_specs, check_vma=False)(
        close, high, low)


def sharded_vwap_backtest(mesh: Mesh, close, volume, window: int, k: float,
                          *, cost: float = 0.0, periods_per_year: int = 252,
                          axis_name: str = TIME_AXIS,
                          t_real: int | None = None):
    """End-to-end VWAP-deviation mean-reversion backtest, TIME axis sharded.

    The volume-weighted composition (``models.vwap`` semantics): rolling
    VWAP is two windowed sums (price x volume and volume) riding ONE
    stacked distributed cumsum + halo, the close's deviation from it is
    z-scored with the same windowed machinery
    (:func:`_windowed_zscore_local` on the derived series), and the band
    machine + PnL tail finish as in Bollinger. Warmup and zero-volume
    windows fall back to ``vwap = close`` (deviation 0), exactly like the
    unsharded NaN-guarded path.

    ``window`` is a static int with ``window <= block length``. Returns
    scalar-per-series :class:`~..ops.metrics.Metrics`, replicated.
    Matches the single-device computation to f32 tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    _check_time_axis(T_pad, n_dev, window, axis_name, "window")
    T = _resolve_t_real(T_pad, t_real)
    halo_w = window
    eps = 1e-12
    k_f = jnp.float32(k)
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk, vol_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        r = _block_returns(close_blk, gidx, axis_name)

        # Both VWAP sums through ONE stacked _cumsum_ext.
        cs, cs_ext = _cumsum_ext(
            jnp.stack([close_blk * vol_blk, vol_blk]), halo_w, axis_name)
        s = _windowed_sum_blk(cs, cs_ext, gidx, window, halo_w)
        pv, v = s[0], s[1]
        valid_w = gidx >= window - 1
        vwap = jnp.where(valid_w & (v > eps), pv / (v + eps), close_blk)
        dev = close_blk - vwap        # 0 through warmup, like the
                                      # unsharded NaN-window fallback
        z = _windowed_zscore_local(dev, gidx, window, halo_w, T,
                                   axis_name, eps=eps)
        valid = gidx >= 2 * window - 2   # rolling.valid_mask(T, 2w - 1)
        z = jnp.where(valid, z, 0.0)
        pos = _band_positions_local(z, jnp.broadcast_to(valid, z.shape),
                                    k_f, jnp.float32(0.0), axis_name)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=out_specs, check_vma=False)(close, volume)


def sharded_macd_backtest(mesh: Mesh, close, fast: int, slow: int,
                          signal: int, *, cost: float = 0.0,
                          periods_per_year: int = 252,
                          axis_name: str = TIME_AXIS,
                          t_real: int | None = None):
    """End-to-end MACD signal-line backtest, TIME axis sharded.

    Pure EMA-chain composition (``models.macd`` semantics): the close is
    demeaned by its GLOBAL first bar (one ``psum`` broadcast — the f32
    error-budget trick of the unsharded model), the fast/slow EMAs and
    the signal-line EMA are three blockwise linear scans with one
    ``(A, B)`` carry pair per chip each, and the trade is
    ``sign(macd - signal_line)`` masked for the ``slow + signal - 1``
    warmup. EMA state is O(1), so only the one-bar return halo constrains
    the block size.

    ``fast``/``slow``/``signal`` are static ints. Returns
    scalar-per-series :class:`~..ops.metrics.Metrics`, replicated.
    Parity with the single-device model is flip-aware: the unsharded path
    evaluates its EMAs with the shift-doubling ladder while the blockwise
    path uses ``associative_scan`` + carry fixup, which rounds ~1e-7
    differently — enough to flip a knife-edge ``sign(macd - sig)``
    crossing (the TRIX caveat class; the parity test bounds flips).
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if fast < 1 or slow < 1 or signal < 1:
        raise ValueError(
            f"spans must be >= 1, got {fast}, {slow}, {signal}")
    T = _resolve_t_real(T_pad, t_real)
    a_fast = jnp.float32(2.0 / (fast + 1.0))
    a_slow = jnp.float32(2.0 / (slow + 1.0))
    a_sig = jnp.float32(2.0 / (signal + 1.0))
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb
        r = _block_returns(close_blk, gidx, axis_name)

        # Demean by the global first bar (models.macd: x = close - close[0];
        # shift-invariant in exact arithmetic, ~100x less f32 rounding).
        c0 = jax.lax.psum(
            jnp.sum(jnp.where(gidx == 0, close_blk, 0.0), axis=-1),
            axis_name)[..., None]
        x = close_blk - c0
        macd = (_ema_local(x, gidx, a_fast, axis_name)
                - _ema_local(x, gidx, a_slow, axis_name))
        sig = _ema_local(macd, gidx, a_sig, axis_name)

        warm = slow + signal - 1
        valid = gidx >= warm - 1      # rolling.valid_mask(T, warm)
        pos = jnp.where(valid, jnp.sign(macd - sig), 0.0)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=out_specs, check_vma=False)(close)


def sharded_obv_backtest(mesh: Mesh, close, volume, window: int, *,
                         cost: float = 0.0, periods_per_year: int = 252,
                         axis_name: str = TIME_AXIS,
                         t_real: int | None = None):
    """End-to-end OBV-trend backtest, TIME axis sharded.

    A *double-accumulation* composition (``models.obv`` semantics): the
    OBV series is a distributed cumsum of the signed volume steps (one
    block-offset ``all_gather``), and its rolling mean is a second
    distributed cumsum over the OBV values with a ``window``-bar halo for
    the lagged read (:func:`_cumsum_ext` + :func:`_windowed_sum_blk` —
    the SMA machinery applied to a derived series). The first-bar volume
    normalizer is one ``psum`` of the chip-0 contribution.

    ``window`` is a static int with ``window <= block length``. Returns
    scalar-per-series :class:`~..ops.metrics.Metrics`, replicated.
    Matches the unsharded ``obv_trend`` strategy backtest to f32
    tolerance.
    """
    from ..ops.metrics import Metrics

    n_dev = mesh.shape[axis_name]
    T_pad = close.shape[-1]
    if T_pad % n_dev:
        raise ValueError(
            f"T={T_pad} not divisible by the {n_dev}-way {axis_name!r} axis")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if window > T_pad // n_dev:
        raise ValueError(
            f"window={window} exceeds the {T_pad // n_dev}-bar block; the "
            "halo exchange needs the window to fit one neighbor block")
    T = _resolve_t_real(T_pad, t_real)
    halo_w = window
    spec = P(*((None,) * (close.ndim - 1) + (axis_name,)))
    rep = P(*((None,) * (close.ndim - 1)))

    def local(close_blk, vol_blk):
        Tb = close_blk.shape[-1]
        gidx = jnp.arange(Tb) + jax.lax.axis_index(axis_name) * Tb

        # ONE one-bar halo exchange serves both the returns and the OBV
        # sign step (collectives are latency-bound; XLA is not guaranteed
        # to CSE two identical ppermutes — the sharded-RSI discipline).
        prev_close = jnp.concatenate(
            [_from_left(close_blk, 1, axis_name), close_blk[..., :-1]],
            axis=-1)
        r = jnp.where(gidx == 0, 0.0,
                      close_blk / jnp.where(gidx == 0, 1.0, prev_close)
                      - 1.0)

        # First-bar volume normalizer, broadcast from the global bar 0.
        v0 = jax.lax.psum(
            jnp.sum(jnp.where(gidx == 0, vol_blk, 0.0), axis=-1),
            axis_name)[..., None]
        v = vol_blk / jnp.where(v0 == 0.0, 1.0, v0)
        # diff[0] = 0 globally (sign(0) = 0).
        step = jnp.where(gidx == 0, 0.0,
                         jnp.sign(close_blk - prev_close)) * v

        # OBV = distributed cumsum of steps; its rolling mean = a second
        # distributed cumsum with a window halo (the double accumulation).
        obv = jnp.cumsum(step, axis=-1)
        obv = obv + _exclusive_block_offset(obv[..., -1],
                                            axis_name)[..., None]
        cs, cs_ext = _cumsum_ext(obv, halo_w, axis_name)
        sma = _windowed_sum_blk(cs, cs_ext, gidx, window,
                                halo_w) / jnp.float32(window)

        valid = gidx >= window - 1   # rolling.valid_mask(T, window)
        pos = jnp.where(valid, jnp.sign(obv - sma), 0.0)
        return _pnl_metrics_local(pos, r, gidx, T, cost=cost,
                                  periods_per_year=periods_per_year,
                                  axis_name=axis_name)

    out_specs = Metrics(*(rep for _ in Metrics._fields))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                         out_specs=out_specs, check_vma=False)(close, volume)
