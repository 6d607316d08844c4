"""Scan-form checkpoints and recurrent-form append steps per kernel family.

One :class:`StreamCarry` holds everything needed to advance a finished
T-bar sweep by a ΔT-bar slice without touching the first T bars again:

- **metric accumulators** (``metric``): the shared tail of every fused
  kernel — net-return moment sums (s1/s2/downside), win/active counts,
  turnover, and the carry-scan equity state (cumulative net, running
  peak, max drawdown) threaded exactly like ``ops.fused._equity_scan``
  threads it between T-blocks (``_advance_metrics`` is its recurrent
  form over the LAST axis). Counts and turnover are f32 sums of exact
  small integers, so a (sweep@T + append@ΔT) merge is bit-exact for
  them; moment sums differ from a cold (T+ΔT) sweep only by one f32
  association boundary, and the equity path by the PR-3 block-boundary
  association budget.
- **signal state** (``state`` + ``metric["pos_last"]``): the band/latch
  machines' 3-state position is Markov in the position itself, so the
  last position column IS the compose state; EMA families additionally
  carry their filter values at the last bar (exact state, advanced with
  the textbook recurrence).
- **raw input tail** (``tail``): the last ``tail_bars`` bars of every
  consumed column — enough support that every windowed indicator value
  on appended bars is recomputed from real data with the generic
  models' own op order. While the tail still covers the whole history
  (short panels), the append replays the models verbatim and appended
  positions are bit-identical to the cold sweep; once the tail is
  partial, windowed indicators recompute on the tail window — the same
  values modulo f32 cumsum association, i.e. the knife-edge flip class
  every substrate A/B in this repo budgets (quantified in the parity
  tests).

``build_carry`` (scan form) and ``append_step`` (recurrent form) share
ONE metric-advance implementation, so the two forms cannot drift: the
cold build is literally one advance over the whole panel from the zero
state.

Numerics contract vs the cold sweep at T+ΔT (tested per family):
positions on appended bars bit-identical while the tail covers history
(and modulo the knife-edge class after), turnover/trades/hit counts
bit-exact where positions match, sum metrics within one f32 association
boundary, equity-path metrics within the PR-3 block-association budget.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import io
import json
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import base as models_base
from ..models import donchian as donchian_mod
from ..models import pairs as pairs_mod
from ..models import stochastic as stoch_mod
from ..models import vwap as vwap_mod
from ..ops import fused as fused_ops
from ..ops import pnl as pnl_mod
from ..ops import rolling
from ..ops.metrics import Metrics
from ..utils import data as data_mod

_EPS = 1e-12


# ---------------------------------------------------------------------------
# Carry container + codec
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamCarry:
    """Persistable checkpoint of a (panel, strategy, param-block) sweep
    after ``n_bars`` bars. Array leaves are jax arrays (device-resident
    when cached at the device level); ``carry_to_bytes`` round-trips the
    whole thing losslessly for the host level / the wire."""

    strategy: str
    grid: dict                      # flat per-combo (P,) float32 axes
    cost: float
    ppy: int
    n_bars: int
    tail: dict                      # field -> (N, K) f32 raw input tail
    state: dict                     # family signal state (EMA values, ...)
    metric: dict                    # shared metric accumulators, (N, P) f32

    @property
    def nbytes(self) -> int:
        return int(sum(int(np.asarray(a).nbytes)
                       for d in (self.grid, self.tail, self.state,
                                 self.metric)
                       for a in d.values()))


def stream_key(strategy: str, grid, cost: float, ppy: int) -> str:
    """Content key of the carry's parameter block: the digest that —
    together with the panel digest — addresses a checkpoint. Canonical
    over axis order (sorted names) and array bytes."""
    h = hashlib.blake2b(digest_size=16)
    h.update(strategy.encode())
    for name in sorted(grid):
        h.update(name.encode())
        h.update(np.ascontiguousarray(np.asarray(grid[name],
                                                 np.float32)).tobytes())
    h.update(np.float32(cost).tobytes())
    h.update(str(int(ppy)).encode())
    return h.hexdigest()


def carry_to_bytes(carry: StreamCarry) -> bytes:
    """Serialize a checkpoint (npz + JSON meta). Lossless: restoring and
    appending bit-matches appending to the never-serialized carry."""
    arrays = {}
    for ns, d in (("g", carry.grid), ("t", carry.tail),
                  ("s", carry.state), ("m", carry.metric)):
        for k, v in d.items():
            arrays[f"{ns}/{k}"] = np.asarray(v)
    meta = json.dumps({"strategy": carry.strategy, "cost": carry.cost,
                       "ppy": carry.ppy, "n_bars": carry.n_bars})
    buf = io.BytesIO()
    np.savez(buf, **{"meta": np.asarray(meta)}, **arrays)
    return buf.getvalue()


def carry_from_bytes(data: bytes) -> StreamCarry:
    with np.load(io.BytesIO(data)) as z:
        meta = json.loads(str(z["meta"]))
        out = {"g": {}, "t": {}, "s": {}, "m": {}}
        for key in z.files:
            if key == "meta":
                continue
            ns, _, name = key.partition("/")
            out[ns][name] = jnp.asarray(z[key])
    return StreamCarry(strategy=meta["strategy"], grid=out["g"],
                      cost=float(meta["cost"]), ppy=int(meta["ppy"]),
                      n_bars=int(meta["n_bars"]), tail=out["t"],
                      state=out["s"], metric=out["m"])


# ---------------------------------------------------------------------------
# Shared metric accumulators (the recurrent form of the kernels' tail)
# ---------------------------------------------------------------------------

def _metric_init(n: int, p: int) -> dict:
    z = jnp.zeros((n, p), jnp.float32)
    return {"s1": z, "s2": z, "dsum": z, "wins": z, "active": z,
            "turnover": z, "pos_last": z, "cum": z,
            "peak": jnp.full((n, p), -jnp.inf, jnp.float32), "mdd": z}


# The equity-state step is fused.py's: the scan form (`_equity_scan`)
# and this recurrent form live next to each other so the carry threading
# cannot drift between the substrates.
_equity_advance = fused_ops._equity_advance


def _advance_metrics(metric: dict, pos, ret, *, cost: float,
                     block: int) -> dict:
    """Fold a ``(N, P, D)`` position slice (and its ``(N, 1|P, D)``
    returns) into the accumulators. The scan form (build) calls this once
    with D = T from the zero state; the recurrent form calls it with
    D = ΔT from the stored state — one implementation, no drift."""
    # Anchor dtypes: a position path built purely from Python-scalar
    # selects (the band-touch machine) is WEAKLY typed f32 — letting it
    # into the carry would make downstream dtype depend on a constant's
    # Python type (kernel-hygiene's weak-type rule caught exactly this).
    pos = jnp.asarray(pos, jnp.float32)
    ret = jnp.asarray(ret, jnp.float32)
    prev = jnp.concatenate([metric["pos_last"][..., None], pos[..., :-1]],
                           axis=-1)
    dpos = jnp.abs(pos - prev)
    net = prev * ret - jnp.float32(cost) * dpos
    down = jnp.minimum(net, 0.0)
    active = jnp.abs(prev) > 0
    wins = (net > 0) & active
    cum, peak, mdd = _equity_advance(net, block, metric["cum"],
                                     metric["peak"], metric["mdd"])
    return {
        "s1": metric["s1"] + jnp.sum(net, axis=-1),
        "s2": metric["s2"] + jnp.sum(net * net, axis=-1),
        "dsum": metric["dsum"] + jnp.sum(down * down, axis=-1),
        "wins": metric["wins"] + jnp.sum(wins.astype(jnp.float32), axis=-1),
        "active": metric["active"] + jnp.sum(active.astype(jnp.float32),
                                             axis=-1),
        "turnover": metric["turnover"] + jnp.sum(dpos, axis=-1),
        "pos_last": pos[..., -1],
        "cum": cum, "peak": peak, "mdd": mdd,
    }


def _finalize_impl(metric: dict, n, *, ppy: int) -> Metrics:
    """Accumulators -> the 9 metrics, replicating
    ``ops.fused._metrics_pack``'s final op order. Kept un-jitted beside
    its jitted wrapper so dbxcert (analysis.certify) re-traces the LIVE
    module code — tracing through the jit wrapper would serve a stale
    cached jaxpr and hide the very edits the contract gate exists to
    catch."""
    n = jnp.float32(n)
    mean = metric["s1"] / n
    var = jnp.maximum(metric["s2"] / n - mean * mean, 0.0)
    std = jnp.sqrt(var)
    ann = jnp.sqrt(jnp.float32(ppy))
    dstd = jnp.sqrt(metric["dsum"] / n)
    hit = metric["wins"] / (metric["active"] + _EPS)
    years = jnp.maximum(n / jnp.float32(ppy), _EPS)
    eq_final = 1.0 + metric["cum"]
    final = jnp.maximum(eq_final, _EPS)
    return Metrics(
        sharpe=mean / (std + _EPS) * ann,
        sortino=mean / (dstd + _EPS) * ann,
        max_drawdown=metric["mdd"],
        total_return=eq_final - 1.0,
        cagr=jnp.power(final, 1.0 / years) - 1.0,
        volatility=std * ann,
        hit_rate=hit,
        n_trades=0.5 * metric["turnover"],
        turnover=metric["turnover"],
    )


_finalize_jit = functools.partial(jax.jit, static_argnames=("ppy",))(
    _finalize_impl)


def finalize(carry: StreamCarry) -> Metrics:
    """The checkpoint's 9 metrics over its whole history, ``(N, P)``."""
    return _finalize_jit(carry.metric, np.float32(carry.n_bars),
                         ppy=carry.ppy)


# ---------------------------------------------------------------------------
# Family registry: tail sizing + partial-tail signal heads
# ---------------------------------------------------------------------------

def _mw(grid, *names) -> int:
    return int(max(int(round(float(np.max(np.asarray(grid[n])))))
                   for n in names))


class _StreamSpec(NamedTuple):
    """One streaming family row: consumed columns, tail sizing, and the
    partial-tail head (None = window replay through the generic model —
    valid for memoryless families whose indicators are shift/scale
    invariant over the tail window)."""

    fields: tuple
    tail_bars: Callable             # grid -> int
    head: Callable | None = None    # (win, D, grid, state, pos0) ->
                                    #   (pos_delta, ret_delta|None, state')


def _band_advance(z, z_entry, z_exit, pos0):
    """Recurrent form of ``ops.signals.band_hysteresis``: advance the
    3-state machine over a ``(N, P, D)`` z slice from the carried
    position. Selection-only (no float arithmetic on the state), so the
    advanced path is bit-identical to the cold machine given the same z."""
    def step(pos, z_t):
        entered = jnp.where(z_t < -z_entry, 1.0,
                            jnp.where(z_t > z_entry, -1.0, 0.0))
        exit_long = (pos > 0) & (z_t >= -z_exit)
        exit_short = (pos < 0) & (z_t <= z_exit)
        held = jnp.where(exit_long | exit_short, 0.0, pos)
        nxt = jnp.where(pos == 0, entered, held)
        return nxt, nxt

    _, pos_t = jax.lax.scan(step, pos0, jnp.moveaxis(z, -1, 0))
    return jnp.moveaxis(pos_t, 0, -1)


def _latch_advance(up, down, pos0):
    """Recurrent form of ``models.donchian._latch`` (valid region only)."""
    def step(pos, inp):
        up_t, down_t = inp
        nxt = jnp.where(up_t, 1.0, jnp.where(down_t, -1.0, pos))
        return nxt, nxt

    xs = (jnp.moveaxis(up, -1, 0), jnp.moveaxis(down, -1, 0))
    _, pos_t = jax.lax.scan(step, pos0, xs)
    return jnp.moveaxis(pos_t, 0, -1)


def _per_lane(fn, rows, grid):
    """vmap ``fn(*single_rows, params)`` over tickers (axis 0) and the
    flat param grid — the same (ticker x param) fan-out the generic sweep
    uses, so indicator op order matches the semantics-defining path."""
    def per_ticker(*r):
        return jax.vmap(lambda p: fn(*r, p))(dict(grid))
    return jax.vmap(per_ticker)(*rows)


def _ohlcv_rows(rows: dict):
    close = rows["close"]
    return data_mod.OHLCV(
        open=rows.get("open", close), high=rows.get("high", close),
        low=rows.get("low", close), close=close,
        volume=rows.get("volume", jnp.ones_like(close)))


def _positions_full(strategy: str, fields: dict, grid):
    """Positions over a full-history window via the generic models —
    ``(N, P, T)`` (pairs also returns beta). THE semantics-defining path:
    whatever it computes is what the cold sweep means."""
    if strategy == "pairs":
        return _per_lane(lambda y, x, p: pairs_mod.pairs_positions(y, x, p),
                         [fields["close"], fields["close2"]], grid)
    strat = models_base.get_strategy(strategy)
    names = [f for f in data_mod.OHLCV._fields if f in fields]

    def fn(*rows, _names=tuple(names)):
        *cols, params = rows
        o = _ohlcv_rows(dict(zip(_names, cols)))
        return strat.positions(o, params)

    return _per_lane(lambda *r: fn(*r), [fields[f] for f in names], grid)


def _pairs_hedged_returns(y, x, beta):
    """``models.pairs.pair_net_returns``'s hedged-return op order."""
    ry = pnl_mod.simple_returns(y)[:, None, :]
    rx = pnl_mod.simple_returns(x)[:, None, :]
    prev_beta = jnp.concatenate(
        [jnp.zeros_like(beta[..., :1]), beta[..., :-1]], axis=-1)
    gross = 1.0 + jnp.abs(prev_beta)
    return (ry - prev_beta * rx) / jnp.maximum(gross, 1.0)


def _extract_state(strategy: str, fields: dict, grid) -> dict:
    """Exact signal state at the window's last bar, from the models' own
    filters (EMA families; everything else is stateless beyond the tail
    + the metric state's last position)."""
    close = fields["close"]
    if strategy == "rsi":
        diff = jnp.diff(close, axis=-1, prepend=close[..., :1])
        gains, losses = jnp.maximum(diff, 0.0), jnp.maximum(-diff, 0.0)
        ag = _per_lane(lambda g, p: rolling.ema(g, alpha=1.0 / p["period"]),
                       [gains], grid)[..., -1]
        al = _per_lane(lambda l, p: rolling.ema(l, alpha=1.0 / p["period"]),
                       [losses], grid)[..., -1]
        return {"ag": ag, "al": al}
    if strategy == "macd":
        def fn(c, p):
            x = c - c[:1]
            ef = rolling.ema_ladder(x, span=p["fast"])
            es = rolling.ema_ladder(x, span=p["slow"])
            esig = rolling.ema_ladder(ef - es, span=p["signal"])
            return ef[-1], es[-1], esig[-1]
        ef, es, esig = _per_lane(fn, [close], grid)
        return {"ef": ef, "es": es, "esig": esig,
                "c0": close[..., :1]}
    if strategy == "trix":
        def fn(c, p):
            e1 = rolling.ema_ladder(c, span=p["span"])
            e2 = rolling.ema_ladder(e1, span=p["span"])
            e3 = rolling.ema_ladder(e2, span=p["span"])
            prev = jnp.concatenate([e3[:1], e3[:-1]], axis=-1)
            trix = e3 / prev - 1.0
            esig = rolling.ema_ladder(trix, span=p["signal"])
            return e1[-1], e2[-1], e3[-1], esig[-1]
        e1, e2, e3, esig = _per_lane(fn, [close], grid)
        return {"e1": e1, "e2": e2, "e3": e3, "esig": esig}
    if strategy == "keltner":
        mid = _per_lane(lambda c, p: rolling.ema(c, span=p["window"]),
                        [close], grid)[..., -1]
        return {"mid": mid}
    return {}


# -- partial-tail heads ------------------------------------------------------
# Every head runs with n_bars > tail_bars(grid) >= max warmup, so every
# delta bar is past warmup for every lane — no validity masks needed.

def _head_bollinger(win, D, grid, state, pos0):
    K = win["close"].shape[-1] - D
    z = _per_lane(lambda c, p: rolling.rolling_zscore(c, p["window"],
                                                      fill=0.0),
                  [win["close"]], grid)[..., K:]
    return _band_advance(z, grid["k"], 0.0, pos0), None, state


def _head_stochastic(win, D, grid, state, pos0):
    K = win["close"].shape[-1] - D
    z = _per_lane(
        lambda h, l, c, p: stoch_mod.stochastic_k(h, l, c, p["window"]),
        [win["high"], win["low"], win["close"]], grid)[..., K:] - 50.0
    return _band_advance(z, grid["band"], 0.0, pos0), None, state


def _head_vwap(win, D, grid, state, pos0):
    K = win["close"].shape[-1] - D

    def fn(c, v, p):
        dev = vwap_mod.vwap_deviation(c, v, p["window"])
        return rolling.rolling_zscore(dev, p["window"], fill=0.0)

    z = _per_lane(fn, [win["close"], win["volume"]], grid)[..., K:]
    return _band_advance(z, grid["k"], 0.0, pos0), None, state


def _head_keltner(win, D, grid, state, pos0):
    close = win["close"]
    K = close.shape[-1] - D
    a = 2.0 / (grid["window"] + 1.0)                         # (P,)

    def step(mid, c_t):                                      # c_t (N, 1)
        mid = (1.0 - a) * mid + a * c_t
        return mid, mid

    xs = jnp.moveaxis(close[..., K:], -1, 0)[..., None]      # (D, N, 1)
    mid_last, mids = jax.lax.scan(step, state["mid"], xs)
    mids = jnp.moveaxis(mids, 0, -1)                         # (N, P, D)
    atr = _per_lane(
        lambda h, l, c, p: rolling.rolling_mean(
            keltner_true_range(h, l, c), p["window"], fill=jnp.nan),
        [win["high"], win["low"], close], grid)[..., K:]
    dev = close[:, None, K:] - mids
    z = jnp.where(atr > _EPS, dev / (atr + _EPS), 0.0)
    return (_band_advance(z, grid["k"], 0.0, pos0), None,
            {"mid": mid_last})


def keltner_true_range(high, low, close):
    from ..models import keltner as keltner_mod
    return keltner_mod.true_range(high, low, close)


def _head_rsi(win, D, grid, state, pos0):
    close = win["close"]
    K = close.shape[-1] - D
    a = 1.0 / grid["period"]                                 # (P,)

    def step(carry, c_t):                                    # c_t (N, 1)
        ag, al, pc = carry
        diff = c_t - pc
        ag = (1.0 - a) * ag + a * jnp.maximum(diff, 0.0)
        al = (1.0 - a) * al + a * jnp.maximum(-diff, 0.0)
        rsi = 100.0 - 100.0 / (1.0 + ag / (al + _EPS))
        return (ag, al, c_t), rsi - 50.0

    xs = jnp.moveaxis(close[..., K:], -1, 0)[..., None]
    (ag, al, _), z = jax.lax.scan(
        step, (state["ag"], state["al"], close[..., K - 1:K]), xs)
    z = jnp.moveaxis(z, 0, -1)
    return (_band_advance(z, grid["band"], 0.0, pos0), None,
            {"ag": ag, "al": al})


def _head_macd(win, D, grid, state, pos0):
    close = win["close"]
    K = close.shape[-1] - D
    af = 2.0 / (grid["fast"] + 1.0)
    as_ = 2.0 / (grid["slow"] + 1.0)
    ag = 2.0 / (grid["signal"] + 1.0)
    c0 = state["c0"]

    def step(carry, c_t):
        ef, es, esig = carry
        x = c_t - c0
        ef = (1.0 - af) * ef + af * x
        es = (1.0 - as_) * es + as_ * x
        macd = ef - es
        esig = (1.0 - ag) * esig + ag * macd
        return (ef, es, esig), jnp.sign(macd - esig)

    xs = jnp.moveaxis(close[..., K:], -1, 0)[..., None]
    (ef, es, esig), pos = jax.lax.scan(
        step, (state["ef"], state["es"], state["esig"]), xs)
    return (jnp.moveaxis(pos, 0, -1), None,
            {"ef": ef, "es": es, "esig": esig, "c0": c0})


def _head_trix(win, D, grid, state, pos0):
    close = win["close"]
    K = close.shape[-1] - D
    a = 2.0 / (grid["span"] + 1.0)
    ag = 2.0 / (grid["signal"] + 1.0)

    def step(carry, c_t):
        e1, e2, e3, esig = carry
        e1 = (1.0 - a) * e1 + a * c_t
        e2 = (1.0 - a) * e2 + a * e1
        e3n = (1.0 - a) * e3 + a * e2
        trix = e3n / e3 - 1.0
        esig = (1.0 - ag) * esig + ag * trix
        return (e1, e2, e3n, esig), jnp.sign(trix - esig)

    xs = jnp.moveaxis(close[..., K:], -1, 0)[..., None]
    (e1, e2, e3, esig), pos = jax.lax.scan(
        step, (state["e1"], state["e2"], state["e3"], state["esig"]), xs)
    return (jnp.moveaxis(pos, 0, -1), None,
            {"e1": e1, "e2": e2, "e3": e3, "esig": esig})


def _donchian_head(hi_src: str, lo_src: str):
    def head(win, D, grid, state, pos0):
        close = win["close"]
        K = close.shape[-1] - D
        hi = _per_lane(
            lambda s, p: rolling.rolling_extrema_traced(
                s, p["window"], max_window=donchian_mod.MAX_WINDOW,
                mode="max", fill=jnp.inf),
            [win[hi_src]], grid)
        lo = _per_lane(
            lambda s, p: rolling.rolling_extrema_traced(
                s, p["window"], max_window=donchian_mod.MAX_WINDOW,
                mode="min", fill=-jnp.inf),
            [win[lo_src]], grid)
        hi_prev = jnp.concatenate(
            [jnp.full_like(hi[..., :1], jnp.inf), hi[..., :-1]], axis=-1)
        lo_prev = jnp.concatenate(
            [jnp.full_like(lo[..., :1], -jnp.inf), lo[..., :-1]], axis=-1)
        c3 = close[:, None, :]
        up = (c3 >= hi_prev)[..., K:]
        down = (c3 <= lo_prev)[..., K:]
        return _latch_advance(up, down, pos0), None, state
    return head


def _head_pairs(win, D, grid, state, pos0):
    y, x = win["close"], win["close2"]
    K = y.shape[-1] - D
    beta, z, _ = _per_lane(
        lambda yy, xx, p: pairs_mod.pair_signals(yy, xx, p["lookback"]),
        [y, x], grid)
    pos = _band_advance(z[..., K:], grid["z_entry"],
                        grid.get("z_exit", 0.0), pos0)
    hr = _pairs_hedged_returns(y, x, beta)[..., K:]
    return pos, hr, state


_STREAM_FAMILIES = {
    "sma_crossover": _StreamSpec(
        ("close",), lambda g: _mw(g, "fast", "slow") + 2),
    "momentum": _StreamSpec(("close",), lambda g: _mw(g, "lookback") + 2),
    "bollinger_touch": _StreamSpec(("close",),
                                   lambda g: _mw(g, "window") + 2),
    "obv_trend": _StreamSpec(("close", "volume"),
                             lambda g: _mw(g, "window") + 2),
    "bollinger": _StreamSpec(("close",), lambda g: _mw(g, "window") + 2,
                             _head_bollinger),
    "stochastic": _StreamSpec(("close", "high", "low"),
                              lambda g: _mw(g, "window") + 2,
                              _head_stochastic),
    "vwap_reversion": _StreamSpec(("close", "volume"),
                                  lambda g: 2 * _mw(g, "window") + 2,
                                  _head_vwap),
    "keltner": _StreamSpec(("close", "high", "low"),
                           lambda g: _mw(g, "window") + 2, _head_keltner),
    "rsi": _StreamSpec(("close",), lambda g: _mw(g, "period") + 2,
                       _head_rsi),
    "macd": _StreamSpec(
        ("close",), lambda g: _mw(g, "slow") + _mw(g, "signal") + 2,
        _head_macd),
    "trix": _StreamSpec(
        ("close",), lambda g: 3 * _mw(g, "span") + _mw(g, "signal") + 2,
        _head_trix),
    "donchian": _StreamSpec(("close",), lambda g: _mw(g, "window") + 3,
                            _donchian_head("close", "close")),
    "donchian_hl": _StreamSpec(("close", "high", "low"),
                               lambda g: _mw(g, "window") + 3,
                               _donchian_head("high", "low")),
    "pairs": _StreamSpec(("close", "close2"),
                         lambda g: 2 * _mw(g, "lookback") + 2, _head_pairs),
}


def supports_strategy(strategy: str) -> bool:
    return strategy in _STREAM_FAMILIES


def stream_fields(strategy: str) -> tuple:
    """OHLCV columns the family's signal head consumes (``close2`` = the
    pairs x leg)."""
    return _STREAM_FAMILIES[strategy].fields


def tail_bars(strategy: str, grid) -> int:
    """Raw-input bars the carry retains: every windowed indicator (and
    its warmup chain) on an appended bar is recomputable from this many
    trailing bars."""
    return _STREAM_FAMILIES[strategy].tail_bars(grid)


# ---------------------------------------------------------------------------
# Scan form (build) + recurrent form (append)
# ---------------------------------------------------------------------------

def _grid_jnp(grid) -> dict:
    return {k: jnp.asarray(np.asarray(v, np.float32).reshape(-1))
            for k, v in grid.items()}


def _single_asset_ret(close):
    return pnl_mod.simple_returns(close)[:, None, :]


def _build_impl(fields, grid, *, strategy: str, cost: float, block: int):
    out = _positions_full(strategy, fields, grid)
    if strategy == "pairs":
        pos, beta = out
        ret = _pairs_hedged_returns(fields["close"], fields["close2"], beta)
    else:
        pos, ret = out, _single_asset_ret(fields["close"])
    n, p = pos.shape[0], pos.shape[1]
    metric = _advance_metrics(_metric_init(n, p), pos, ret, cost=cost,
                              block=block)
    return metric, _extract_state(strategy, fields, grid)


# Scan form, jitted for serving (the un-jitted body is the certify trace
# target — see _finalize_impl's rationale).
_build_jit = functools.partial(
    jax.jit, static_argnames=("strategy", "cost", "block"))(_build_impl)


def _append_impl(tail, delta, grid, state, metric, *, strategy: str,
                 cost: float, block: int, D: int, full_cover: bool,
                 K_new: int):
    win = {f: jnp.concatenate([tail[f], delta[f]], axis=-1) for f in tail}
    K = win["close"].shape[-1] - D
    spec = _STREAM_FAMILIES[strategy]
    if full_cover or spec.head is None:
        out = _positions_full(strategy, win, grid)
        if strategy == "pairs":
            pos_w, beta = out
            ret_d = _pairs_hedged_returns(win["close"], win["close2"],
                                          beta)[..., K:]
        else:
            pos_w, ret_d = out, None
        pos_d = pos_w[..., K:]
        state = _extract_state(strategy, win, grid) if full_cover else state
    else:
        pos_d, ret_d, state = spec.head(win, D, grid, state,
                                        metric["pos_last"])
    if ret_d is None:
        ret_d = _single_asset_ret(win["close"])[..., K:]
    metric = _advance_metrics(metric, pos_d, ret_d, cost=cost, block=block)
    new_tail = {f: win[f][..., -K_new:] for f in win}
    return new_tail, state, metric


# Recurrent form, jitted for serving.
_append_jit = functools.partial(
    jax.jit, static_argnames=("strategy", "cost", "block", "D",
                              "full_cover", "K_new"))(_append_impl)


# Host-side unroll bound for the blocked equity advance: each block
# emits its own prefix ops, and XLA-CPU's compile wall grows with the
# emitted block count far faster than Mosaic's (the kernels keep 256).
# Looser blocks only move f32 association inside the PR-3 budget.
_HOST_MAX_BLOCKS = 32


def _block(n: int, epilogue: str | None) -> int:
    n = max(n, 1)
    epi = fused_ops._resolve_epilogue(epilogue)
    if epi == "ladder":
        return n                   # one block: the full-length scan
    b = fused_ops._scan_block(n, epi)
    while -(-n // b) > _HOST_MAX_BLOCKS:
        b *= 2
    return b


def _np_fields(fields: dict) -> dict:
    return {k: jnp.asarray(np.asarray(v, np.float32)) for k, v in
            fields.items()}


def build_carry(strategy: str, fields: dict, grid, *, cost: float = 0.0,
                periods_per_year: int = 252,
                epilogue: str | None = None) -> StreamCarry:
    """Scan form: run the full ``(N, T)`` panel once, return the
    checkpoint. ``fields`` maps consumed column names (``close`` [+
    ``high``/``low``/``volume``; ``close2`` for pairs]) to ``(N, T)``
    arrays; ``grid`` is the flat per-combo axes dict (product order)."""
    if strategy not in _STREAM_FAMILIES:
        raise ValueError(f"strategy {strategy!r} has no streaming family; "
                         f"known: {sorted(_STREAM_FAMILIES)}")
    spec = _STREAM_FAMILIES[strategy]
    missing = [f for f in spec.fields if f not in fields]
    if missing:
        raise ValueError(f"streaming {strategy} needs fields {missing}")
    fields = {f: v for f, v in _np_fields(fields).items()
              if f in spec.fields}
    grid_np = {k: np.asarray(v, np.float32).reshape(-1)
               for k, v in grid.items()}
    gj = _grid_jnp(grid_np)
    T = int(fields["close"].shape[-1])
    metric, state = _build_jit(fields, gj, strategy=strategy,
                               cost=float(cost),
                               block=_block(T, epilogue))
    K = min(T, tail_bars(strategy, grid_np))
    tail = {f: v[..., -K:] for f, v in fields.items()}
    return StreamCarry(strategy=strategy, grid=grid_np, cost=float(cost),
                      ppy=int(periods_per_year), n_bars=T, tail=tail,
                      state=state, metric=metric)


def append_step(carry: StreamCarry, delta_fields: dict, *,
                epilogue: str | None = None) -> StreamCarry:
    """Recurrent form (the ``_append_step`` of each registered family):
    advance a checkpoint by a ``(N, D)`` bar slice in O(D) work. Returns
    a NEW carry (the input is not mutated — retried jobs can re-advance
    the stored base safely)."""
    spec = _STREAM_FAMILIES[carry.strategy]
    delta = {f: v for f, v in _np_fields(delta_fields).items()
             if f in spec.fields}
    missing = [f for f in spec.fields if f not in delta]
    if missing:
        raise ValueError(
            f"append for {carry.strategy} needs delta fields {missing}")
    D = int(delta["close"].shape[-1])
    if D < 1:
        raise ValueError("empty delta slice")
    K = int(carry.tail["close"].shape[-1])
    tb = tail_bars(carry.strategy, carry.grid)
    full_cover = carry.n_bars == K      # tail still holds ALL history
    n_new = carry.n_bars + D
    K_new = min(n_new, tb)
    tail, state, metric = _append_jit(
        carry.tail, delta, _grid_jnp(carry.grid), carry.state,
        carry.metric, strategy=carry.strategy, cost=carry.cost,
        block=_block(D, epilogue), D=D, full_cover=full_cover,
        K_new=K_new)
    return StreamCarry(strategy=carry.strategy, grid=carry.grid,
                      cost=carry.cost, ppy=carry.ppy, n_bars=n_new,
                      tail=tail, state=state, metric=metric)


# Alias matching the kernel-registry naming in the design docs: the
# recurrent entry the lint layer traces per family.
_append_step = append_step


_PROBE_DELTA_BARS = 4


@functools.lru_cache(maxsize=None)
def _probe_inputs(strategy: str):
    """Tiny concrete (carry, delta, grid) for kernel-hygiene tracing —
    cached per family (the build compiles once; the trace itself is
    re-run per epilogue substrate and never compiles)."""
    spec = _STREAM_FAMILIES[strategy]
    axes = {"fast": [2.0], "slow": [5.0], "window": [3.0], "k": [1.0],
            "lookback": [3.0], "period": [3.0], "band": [20.0],
            "signal": [2.0], "span": [2.0], "z_entry": [1.0],
            "z_exit": [0.0]}
    strat_axes = {
        "sma_crossover": ("fast", "slow"), "momentum": ("lookback",),
        "bollinger": ("window", "k"), "bollinger_touch": ("window", "k"),
        "obv_trend": ("window",), "stochastic": ("window", "band"),
        "vwap_reversion": ("window", "k"), "keltner": ("window", "k"),
        "rsi": ("period", "band"), "macd": ("fast", "slow", "signal"),
        "trix": ("span", "signal"), "donchian": ("window",),
        "donchian_hl": ("window",), "pairs": ("lookback", "z_entry",
                                              "z_exit"),
    }[strategy]
    grid = {a: np.asarray(axes[a], np.float32) for a in strat_axes}
    rng = np.random.default_rng(7)
    T, D = tail_bars(strategy, grid) + 6, _PROBE_DELTA_BARS

    def series():
        walk = np.cumsum(rng.standard_normal(T + D) * 0.5)
        return (100.0 + walk).astype(np.float32)[None, :]

    close = series()
    fields = {}
    for f in spec.fields:
        fields[f] = {"close": close, "high": close * 1.01,
                     "low": close * 0.99,
                     "volume": np.full_like(close, 1e4),
                     "close2": series() * 0.9}[f]
    base = {f: v[..., :T] for f, v in fields.items()}
    carry = build_carry(strategy, base, grid)
    delta = {f: np.asarray(v[..., T:]) for f, v in fields.items()}
    return carry, delta, grid, base


def hygiene_probe(strategy: str):
    """``(fn, args)`` for dbxlint kernel-hygiene: ``fn(*args)`` traces one
    recurrent append step (partial-tail signal head + metric advance +
    finalize) over tiny concrete inputs. The block schedule resolves the
    active ``DBX_EPILOGUE`` at call time, so the rule's substrate sweep
    traces both epilogues like the fused kernels'."""
    carry, delta, grid, _ = _probe_inputs(strategy)
    D = _PROBE_DELTA_BARS
    epi_block = _block(D, None)
    K_new = int(carry.tail["close"].shape[-1])

    def fn(tail, delta_a, state, metric):
        new_tail, new_state, new_metric = _append_impl(
            tail, delta_a, _grid_jnp(grid), state, metric,
            strategy=strategy, cost=0.0, block=epi_block, D=D,
            full_cover=False, K_new=K_new)
        m = _finalize_impl(new_metric, jnp.float32(carry.n_bars + D),
                           ppy=252)
        return tuple(m) + tuple(
            new_tail[k] for k in sorted(new_tail)) + tuple(
            new_state[k] for k in sorted(new_state)) + tuple(
            new_metric[k] for k in sorted(new_metric))

    args = [{k: np.asarray(v) for k, v in carry.tail.items()}, delta,
            {k: np.asarray(v) for k, v in carry.state.items()},
            {k: np.asarray(v) for k, v in carry.metric.items()}]
    return fn, args


# ---------------------------------------------------------------------------
# dbxcert probes: the certified streaming cones with LABELED outputs
# ---------------------------------------------------------------------------

# Carry accumulators that are f32 sums/holds of exact small integers by
# the documented carry contract (positions in {-1,0,1}, bool-cast win/
# active counts, |Δpos| turnover increments): dbxcert seeds the append
# form's inputs with this integrality hint so the analyzer can prove the
# int-exact merge guarantee the parity tests pin empirically.
_INTEGRAL_CARRY_KEYS = frozenset(
    {"wins", "active", "turnover", "pos_last"})


def certify_probe(strategy: str, *, form: str, epilogue: str | None = None):
    """``(fn, args, integral_keys)`` for dbxcert (analysis.certify).

    ``fn(*args)`` traces one certified cone of ``strategy`` — ``form``
    is ``"build_carry"`` (scan form over the full tiny panel from the
    zero state) or ``"append_step"`` (recurrent form over a ΔT slice
    from the stored carry) — returning a DICT so every output is
    addressable by a stable label in ``numerics.contract.json``
    (``metrics/<name>`` the 9 public metrics, ``metric/<k>`` the
    accumulators, ``state/<k>`` family signal state, ``tail/<k>`` the
    raw-input tail). The epilogue substrate is passed explicitly (no env
    mutation); the un-jitted impl bodies are traced so a live edit is
    always seen. ``integral_keys`` names input dict keys the analyzer
    may assume integer-valued (the carry contract's hints)."""
    if form not in ("build_carry", "append_step"):
        raise ValueError(f"unknown certify form {form!r}")
    carry, delta, grid, base_fields = _probe_inputs(strategy)
    gj = _grid_jnp(grid)

    def _label(m: Metrics, metric: dict, state: dict, extra: dict) -> dict:
        out = {f"metrics/{k}": getattr(m, k) for k in Metrics._fields}
        out.update({f"metric/{k}": v for k, v in metric.items()})
        out.update({f"state/{k}": v for k, v in state.items()})
        out.update(extra)
        return out

    if form == "append_step":
        D = _PROBE_DELTA_BARS
        block = _block(D, epilogue)
        K_new = int(carry.tail["close"].shape[-1])

        def fn(tail, delta_a, state, metric):
            new_tail, new_state, new_metric = _append_impl(
                tail, delta_a, gj, state, metric, strategy=strategy,
                cost=0.001, block=block, D=D, full_cover=False,
                K_new=K_new)
            m = _finalize_impl(new_metric, jnp.float32(carry.n_bars + D),
                               ppy=252)
            return _label(m, new_metric, new_state,
                          {f"tail/{k}": v for k, v in new_tail.items()})

        args = [{k: np.asarray(v) for k, v in carry.tail.items()},
                dict(delta),
                {k: np.asarray(v) for k, v in carry.state.items()},
                {k: np.asarray(v) for k, v in carry.metric.items()}]
        return fn, args, _INTEGRAL_CARRY_KEYS

    T = int(base_fields["close"].shape[-1])
    block = _block(T, epilogue)

    def fn(fields):
        metric, state = _build_impl(fields, gj, strategy=strategy,
                                    cost=0.001, block=block)
        m = _finalize_impl(metric, jnp.float32(T), ppy=252)
        return _label(m, metric, state, {})

    return fn, [dict(base_fields)], frozenset()
