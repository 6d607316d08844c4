"""Rolling-window indicators as O(T) cumulative-sum kernels.

The reference never implements any indicator math (its compute path is a sleep
stub, reference ``src/worker/process.rs:21-25``); its north-star replacement is
"indicator construction (rolling SMA/std, rolling OLS)" run as fused jit+vmap
kernels (``BASELINE.json`` north_star). This module is that indicator layer.

Design notes (TPU-first):

- **Time is the last axis.** Arrays are ``(..., T)`` so the bar-time axis lands
  on TPU lanes (128-wide) and every op below is a fused VPU elementwise pass.
- **O(T) via cumulative sums**, not O(T*W) via explicit windows: a rolling sum
  over window ``w`` is ``cs[t] - cs[t-w]`` on the inclusive prefix sum. The
  shifted read uses a clipped ``take`` so that ``w`` may be a *traced* scalar —
  this is what lets a parameter sweep ``vmap`` over thousands of window lengths
  without recompilation or dynamic shapes.
- **Compensated prefix sums** (:func:`prefix_sum2`): a plain f32 prefix sum
  carries a rounding error that grows with the history, and the difference
  ``cs[t] - cs[t-w]`` inherits it whole — on 1,260 daily bars ~1% of SMA
  crossover and ~1–4% of Bollinger / pairs Sharpe cells differed from a
  float64 run, and the answer hung on how the compiler associated the scan
  (the fused kernels and this module disagreed on the chip wherever their
  shapes differed). The prefix is carried as an unevaluated pair
  ``hi + lo``; each windowed sum is ``(hi[t] - hi[t-w]) + (lo[t] - lo[t-w])``,
  accurate to a few ulps of the window sum whatever the scan order.
- **Numerical stability in f32**: variance via ``E[x^2] - E[x]^2`` on raw
  price levels (~1e2) catastrophically cancels in float32. All second-moment
  ops first subtract the per-series mean (a constant shift changes neither
  variance nor covariance); means are shifted back where needed.
- Warmup positions ``t < w-1`` are invalid. Ops return them filled with
  ``fill`` (default NaN) and :func:`valid_mask` gives the boolean mask; PnL
  code multiplies positions by the mask instead of branching.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array


def _shifted(cs: Array, w, *, fill=0.0) -> Array:
    """Return ``cs[..., t - w]`` along the last axis, ``fill`` where ``t < w``.

    ``w`` may be a Python int or a traced scalar. Implemented with a clipped
    gather so the shape stays static under ``vmap`` over ``w``.
    """
    T = cs.shape[-1]
    idx = jnp.arange(T) - jnp.asarray(w)
    gather_idx = jnp.clip(idx, 0, T - 1).astype(jnp.int32)
    taken = jnp.take(cs, gather_idx, axis=-1)
    return jnp.where(idx >= 0, taken, fill)


def valid_mask(T: int, window) -> Array:
    """Boolean ``(T,)`` mask: True where a ``window``-bar indicator is defined.

    Broadcasts against any ``(..., T)`` indicator array.
    """
    return jnp.arange(T) >= window - 1


def prefix_sum2(x: Array) -> tuple[Array, Array]:
    """Compensated inclusive prefix sum along the last axis: ``(hi, lo)``
    with ``hi + lo`` the exact prefix sum up to the (tiny) rounding of
    ``lo``'s own scan.

    ``hi`` is the plain ``cumsum`` in whatever order the backend scans.
    Each step's rounding ``prev + x[t] - hi[t]`` is recovered exactly: the
    two-sum of ``hi[t-1] + x[t]`` gives ``s`` and its error, and ``s -
    hi[t]`` is exact (Sterbenz) because both round the same sum. ``lo`` is
    the prefix sum of those errors, so the pair telescopes to the exact sum
    independently of the association of either scan. Every windowed sum
    in this repo — generic, fused, paged, time-sharded — differences these
    pairs (:func:`window_diff`)."""
    hi = jnp.cumsum(x, axis=-1)
    prev = jnp.concatenate([jnp.zeros_like(hi[..., :1]), hi[..., :-1]],
                           axis=-1)
    s, err = two_sum(prev, x)
    return hi, jnp.cumsum((s - hi) + err, axis=-1)


def two_sum(a, b):
    """``(s, err)`` with ``s = fl(a + b)`` and ``a + b == s + err`` exactly
    (Knuth's branch-free two-sum)."""
    s = a + b
    v = s - a
    return s, (a - (s - v)) + (b - v)


def window_diff(hi, lo, hi_lag, lo_lag):
    """Windowed sum from a :func:`prefix_sum2` pair and its lagged read:
    the ``hi`` difference is exact or rounds at the window sum's own scale,
    and ``lo`` carries what the ``hi`` scan dropped."""
    return (hi - hi_lag) + (lo - lo_lag)


# ---------------------------------------------------------------------------
# Double-f32 window moments for the rolling OLS
#
# The hedge ratio's covariance is ``sxy - sx sy / w`` on legs centred by
# their FULL-history means; on price series a 10–50-bar window's mean sits
# many local standard deviations from that centre, so the subtraction
# cancels ~2 orders of magnitude and a last-bit change anywhere upstream
# (a different reduction order for the centre, a fused multiply-add) moved
# beta by up to ~1e-3 and flipped pairs entries between two compilations
# of one formula. These helpers carry each window moment as an unevaluated
# f32 pair (``s + e``) built from exact products (:func:`two_prod`) and
# compensated prefix sums, and form ``cov`` / ``var`` in that precision:
# the cancellation then costs nothing, and beta is the same to a few ulps
# whichever path or compiler computed it.
# ---------------------------------------------------------------------------

def two_prod(a, b):
    """``(p, err)`` with ``p = fl(a * b)`` and ``a * b == p + err`` exactly
    (Dekker's split product; needs no fused multiply-add)."""
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def _split(a):
    nbits = jnp.finfo(a.dtype).nmant + 1
    c = jnp.asarray(2.0 ** ((nbits + 1) // 2) + 1.0, a.dtype) * a
    ah = c - (c - a)
    return ah, a - ah


def prefix_prod2(a, b) -> tuple[Array, Array]:
    """:func:`prefix_sum2` of the exact products ``a * b``: the pair's
    ``lo`` also carries each product's rounding error."""
    p, err = two_prod(a, b)
    hi, lo = prefix_sum2(p)
    return hi, lo + jnp.cumsum(err, axis=-1)


def window_sum2(hi, lo, hi_lag, lo_lag):
    """:func:`window_diff` kept as an unevaluated pair ``(s, e)``."""
    s, e = two_sum(hi, -hi_lag)
    return s, e + (lo - lo_lag)


def ols_slope2(sx, sy, sxx, sxy, w, eps):
    """OLS slope ``cov / (var + eps)`` from :func:`window_sum2` pairs of the
    centred legs' window moments, with ``cov = sxy - sx sy / w`` and ``var
    = sxx - sx^2 / w`` formed in double-f32."""
    def centred(s2, a, b):
        p, pe = two_prod(a[0], b[0])
        pe = pe + (a[0] * b[1] + a[1] * b[0])
        q = p / w                          # (p + pe) / w as q + qe
        r, re = two_prod(q, w)
        qe = ((p - r) - re + pe) / w
        d, e = two_sum(s2[0], -q)
        return d + ((e - qe) + s2[1])
    cov = centred(sxy, sx, sy)
    var = jnp.maximum(centred(sxx, sx, sx), 0.0)
    return cov / (var + eps)


def vwap_deviation2(close, pv, v, eps):
    """``close - pv / v``: the close's deviation from a VWAP whose window
    sums of ``close * volume`` (``pv``) and of volume (``v``) are
    :func:`window_sum2` pairs, formed as ``(close * v - pv) / v`` in
    double-f32, and 0 where the window holds no volume.

    The direct form subtracts two prices that agree to a bar's move: at
    minute bars (~5e-4 of the price) the f32 rounding of the VWAP alone
    was ~1e-4 of the deviation, and over a year of minutes 1-3 % of a
    VWAP sweep's cells flipped a band entry against a float64 run. Here
    ``close * v`` is an exact product pair and the difference of two
    pairs cancels exactly, so the deviation keeps the precision of the
    window sums."""
    p, pe = two_prod(close, v[0])
    d, de = two_sum(p, -pv[0])
    num = d + (de + ((pe + close * v[1]) - pv[1]))
    vol = v[0] + v[1]
    return jnp.where(vol > eps, num / (vol + eps), 0.0)


def rolling_sum(x: Array, window, *, fill=jnp.nan) -> Array:
    """Rolling sum over the trailing ``window`` bars (inclusive), same length.

    ``out[..., t] = sum(x[..., t-window+1 : t+1])``; warmup -> ``fill``.
    """
    hi, lo = prefix_sum2(x)
    lag = _shifted(jnp.stack([hi, lo]), window)
    out = window_diff(hi, lo, lag[0], lag[1])
    return _mask_warmup(out, window, fill)


def _mask_warmup(out: Array, window, fill) -> Array:
    T = out.shape[-1]
    return jnp.where(valid_mask(T, window), out, fill)


def rolling_mean(x: Array, window, *, fill=jnp.nan) -> Array:
    """Rolling mean (SMA) over the trailing ``window`` bars."""
    return rolling_sum(x, window, fill=fill) / jnp.asarray(window, x.dtype)


def _centered(x: Array) -> Array:
    # Constant per-series shift: preserves variances/covariances, kills the
    # float32 cancellation between E[x^2] and E[x]^2 for price-level inputs.
    return x - jnp.mean(x, axis=-1, keepdims=True)


def rolling_var(x: Array, window, *, ddof: int = 0, fill=jnp.nan) -> Array:
    """Rolling population (ddof=0) or sample (ddof=1) variance."""
    xc = _centered(x)
    w = jnp.asarray(window, x.dtype)
    s1 = rolling_sum(xc, window, fill=jnp.nan)
    s2 = rolling_sum(xc * xc, window, fill=jnp.nan)
    var = (s2 - s1 * s1 / w) / (w - ddof)
    var = jnp.maximum(var, 0.0)  # clamp tiny negative f32 residue
    return _mask_warmup(var, window, fill)


def rolling_std(x: Array, window, *, ddof: int = 0, fill=jnp.nan) -> Array:
    """Rolling standard deviation."""
    return jnp.sqrt(rolling_var(x, window, ddof=ddof, fill=fill))


def rolling_zscore(x: Array, window, *, ddof: int = 0, eps=1e-12,
                   fill=jnp.nan) -> Array:
    """``(x - rolling_mean) / rolling_std`` — the Bollinger/pairs entry signal."""
    m = rolling_mean(x, window, fill=jnp.nan)
    s = rolling_std(x, window, ddof=ddof, fill=jnp.nan)
    z = (x - m) / (s + eps)
    return _mask_warmup(z, window, fill)


def rolling_ols(y: Array, x: Array, window, *, eps=1e-12, fill=jnp.nan):
    """Rolling ordinary least squares of ``y`` on ``x`` (with intercept).

    Closed form from windowed moments (all O(T) cumsum differences)::

        beta_t  = cov_w(x, y) / var_w(x)
        alpha_t = mean_w(y) - beta_t * mean_w(x)

    Returns ``(alpha, beta)``, each shaped like ``y``. This is the
    linear-regression kernel behind the pairs-trade config
    (``BASELINE.json`` configs[3]).
    """
    w, mx, my, _, _, sx, sy, beta = _ols_moments(y, x, window, eps)
    # Means of the *uncentered* series: mean_w(x) = sx/w + mx.
    alpha = (sy / w + my) - beta * (sx / w + mx)
    return _mask_warmup(alpha, window, fill), _mask_warmup(beta, window, fill)


def rolling_ols_residual(y: Array, x: Array, window, *, eps=1e-12,
                         fill=jnp.nan):
    """``(beta, resid)``: :func:`rolling_ols`'s hedge ratio and the residual
    ``y - (alpha + beta x)``, evaluated as ``(yc - mean_w(yc)) - beta (xc -
    mean_w(xc))`` on the series-centred legs — the same value, without
    subtracting ``alpha + beta x`` at price level (~1e2), where beta's
    last-bit rounding alone moved the residual by ~1e-3 of its scale and
    flipped pairs entries between two compilations of one formula."""
    w, _, _, xc, yc, sx, sy, beta = _ols_moments(y, x, window, eps)
    resid = (yc - sy / w) - beta * (xc - sx / w)
    return _mask_warmup(beta, window, fill), _mask_warmup(resid, window, fill)


def _ols_moments(y: Array, x: Array, window, eps):
    """Windowed moments of the series-centred legs and the OLS slope
    (:func:`ols_slope2`): ``(w, mx, my, xc, yc, sx, sy, beta)``, ``sx`` /
    ``sy`` as plain window sums."""
    w = jnp.asarray(window, y.dtype)
    mx = jnp.mean(x, axis=-1, keepdims=True)
    my = jnp.mean(y, axis=-1, keepdims=True)
    xc, yc = x - mx, y - my

    pairs = [prefix_sum2(xc), prefix_sum2(yc), prefix_prod2(xc, xc),
             prefix_prod2(xc, yc)]
    stacked = jnp.stack([a for pair in pairs for a in pair])
    lag = _shifted(stacked, window)
    sx, sy, sxx, sxy = (window_sum2(stacked[2 * i], stacked[2 * i + 1],
                                    lag[2 * i], lag[2 * i + 1])
                        for i in range(4))
    beta = ols_slope2(sx, sy, sxx, sxy, w, eps)
    return w, mx, my, xc, yc, sx[0] + sx[1], sy[0] + sy[1], beta


def ema(x: Array, *, span=None, alpha=None, fill=None) -> Array:
    """Exponential moving average via a parallel (associative) scan.

    ``y[t] = (1-a) * y[t-1] + a * x[t]``, ``y[0] = x[0]``, with
    ``a = 2/(span+1)`` when ``span`` is given. A first-order linear recurrence
    is associative under ``(A2,B2) o (A1,B1) = (A1*A2, A2*B1 + B2)``, so XLA
    evaluates it in O(log T) depth on the VPU instead of a serial loop —
    the TPU-idiomatic replacement for a per-bar Python loop.

    ``span``/``alpha`` may be traced scalars (vmap over decay grids).
    """
    if (span is None) == (alpha is None):
        raise ValueError("pass exactly one of span= or alpha=")
    if alpha is None:
        alpha = 2.0 / (jnp.asarray(span, x.dtype) + 1.0)
    a = jnp.broadcast_to(jnp.asarray(1.0 - alpha, x.dtype), x.shape)
    b = x * alpha
    # y[0] = x[0] exactly: make the first element's recurrence y0 = 0*prev + x0.
    t0 = jnp.arange(x.shape[-1]) == 0
    a = jnp.where(t0, 0.0, a)
    b = jnp.where(t0, x, b)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    _, y = jax.lax.associative_scan(combine, (a, b), axis=-1)
    return y


def ema_ladder(x: Array, *, span=None, alpha=None) -> Array:
    """Same EMA recurrence as :func:`ema`, evaluated as a Hillis–Steele
    shift-doubling ladder instead of ``lax.associative_scan``.

    ~log2(T) elementwise passes built from pad-shifts, combining with the
    same ``(A2,B2) o (A1,B1) = (A1*A2, A2*B1 + B2)`` monoid. Two reasons to
    pick this over :func:`ema`:

    - it is the *exact rounding twin* of the fused kernels' in-kernel EMA
      (``ops.fused._ema_ladder`` / ``_ema_rows``), so a generic-path model
      built on it agrees with its fused kernel to the last knife edge
      (associative_scan's Blelloch-style recursion rounds differently at
      ~1e-7, which is enough to flip a ``sign(a - b)`` crossing);
    - XLA compiles the unrolled shift ladder far faster than the scan's
      deep slice graph (measured ~30x on the bench shape) at equal runtime.

    ``span``/``alpha`` may be traced scalars (vmap over decay grids).
    """
    if (span is None) == (alpha is None):
        raise ValueError("pass exactly one of span= or alpha=")
    if alpha is None:
        alpha = 2.0 / (jnp.asarray(span, x.dtype) + 1.0)
    T = x.shape[-1]
    t0 = jnp.arange(T) == 0
    a = jnp.broadcast_to(jnp.asarray(1.0 - alpha, x.dtype), x.shape)
    A = jnp.where(t0, 0.0, a)                 # y[0] = x[0] exactly
    B = jnp.where(t0, x, x * alpha)
    step = 1
    while step < T:
        # Shift the (A, B) pairs down the time axis, filling with the
        # monoid identity (A=1, B=0), and fold into the running prefix.
        Ae = jnp.concatenate(
            [jnp.ones_like(A[..., :step]), A[..., :-step]], axis=-1)
        Be = jnp.concatenate(
            [jnp.zeros_like(B[..., :step]), B[..., :-step]], axis=-1)
        A, B = Ae * A, A * Be + B
        step *= 2
    return B


def obv_series(close, volume):
    """Normalized on-balance volume, shape ``(..., T)``; ``obv[0] = 0``.

    ``obv[t] = sum_{s<=t} sign(close[s] - close[s-1]) * v[s]`` with
    ``v = volume / volume[..., :1]`` (zero-guarded). The first-bar
    normalization keeps the double accumulation (this cumsum, then a
    windowed mean of it) at O(1) magnitudes instead of raw-volume ~1e6
    scale; the traded quantity ``sign(obv - sma)`` is invariant under the
    scaling. This is the ONE definition both the generic model
    (``models.obv``) and the fused kernel prep evaluate — shared so the
    two paths stay rounding twins by construction.
    """
    v0 = volume[..., :1]
    v = volume / jnp.where(v0 == 0.0, 1.0, v0)
    step = jnp.sign(jnp.diff(close, axis=-1, prepend=close[..., :1])) * v
    return jnp.cumsum(step, axis=-1)


def _static_window(window, name: str) -> int:
    if not isinstance(window, (int,)):
        raise TypeError(
            f"{name} requires a static (Python int) window; got {type(window)}. "
            "Rolling extrema have no cumsum form — sweep windows with a Python "
            "loop / jnp.stack over static values instead of vmap."
        )
    return int(window)


def rolling_extrema_traced(x: Array, window, *, max_window: int,
                           mode: str = "max", fill=jnp.nan) -> Array:
    """Rolling max/min with a *traced* window, bounded by ``max_window``.

    Rolling extrema have no cumsum form, so a traced window cannot use the
    doubling trick (:func:`rolling_max`). Instead each output reduces a
    masked ``(T, max_window)`` windowed view — O(T * max_window) work, but
    fully vectorized and vmap-able over window grids. Use the static-window
    versions when the window is known at trace time.
    """
    if mode not in ("max", "min"):
        raise ValueError(f"mode must be 'max' or 'min', got {mode!r}")
    T = x.shape[-1]
    offs = jnp.arange(max_window)                       # 0 .. W-1 lags
    idx = jnp.arange(T)[:, None] - offs[None, :]        # (T, W)
    neutral = -jnp.inf if mode == "max" else jnp.inf
    views = jnp.take(x, jnp.clip(idx, 0, T - 1).astype(jnp.int32), axis=-1)
    ok = (idx >= 0) & (offs[None, :] < jnp.asarray(window))
    views = jnp.where(ok, views, neutral)
    out = jnp.max(views, axis=-1) if mode == "max" else jnp.min(views, axis=-1)
    # A traced window larger than the static bound cannot raise here — poison
    # the output instead of silently truncating the lookback.
    out = jnp.where(jnp.asarray(window) <= max_window, out, jnp.nan)
    return _mask_warmup(out, window, fill)


def rolling_max(x: Array, window, *, fill=jnp.nan) -> Array:
    """Rolling max over trailing ``window`` bars (static window).

    Doubling trick: O(T log W) fused elementwise maxes, no gather loops —
    the Donchian-channel building block.
    """
    w = _static_window(window, "rolling_max")
    out = x
    span = 1  # out[t] currently covers x[t-span+1 .. t]
    while span < w:
        step = min(span, w - span)
        out = jnp.maximum(out, _shifted(out, step, fill=-jnp.inf))
        span += step
    return _mask_warmup(out, w, fill)


def rolling_min(x: Array, window, *, fill=jnp.nan) -> Array:
    """Rolling min over trailing ``window`` bars (static window)."""
    w = _static_window(window, "rolling_min")
    out = x
    span = 1
    while span < w:
        step = min(span, w - span)
        out = jnp.minimum(out, _shifted(out, step, fill=jnp.inf))
        span += step
    return _mask_warmup(out, w, fill)
