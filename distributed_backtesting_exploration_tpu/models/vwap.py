"""VWAP-deviation mean-reversion (stateful): the volume-weighted family.

Rolling VWAP over the trailing ``window`` bars is
``sum(close * volume) / sum(volume)`` — two O(T) cumsum-difference rolling
sums. The trade: z-score the close's deviation from VWAP (std of the
deviation over the same window) and run the shared band machine — enter
when price stretches ``k`` deviations from the volume-weighted anchor,
exit when it re-crosses it. The deviation is formed in double-f32
(:func:`vwap_deviation`): at minute bars it is ~5e-4 of the price.

This is the first family whose signal consumes the ``volume`` field, so
the OHLCV panel's non-close columns carry real information through the
sweep engine (every panel op is already struct-of-arrays; nothing changes
shape-wise).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops import rolling, signals
from .base import Strategy, register


def rolling_vwap(close, volume, window, *, eps=1e-12):
    """Trailing-``window`` volume-weighted average price; ``(..., T)``.

    ``window`` may be traced (vmap over window grids). Bars with zero total
    volume in the window fall back to the plain close (deviation 0).
    """
    pv = rolling.rolling_sum(close * volume, window, fill=jnp.nan)
    v = rolling.rolling_sum(volume, window, fill=jnp.nan)
    return jnp.where(v > eps, pv / (v + eps), close)


def vwap_deviation(close, volume, window, *, eps=1e-12):
    """``close - rolling_vwap(close, volume, window)``, formed without the
    cancellation of two nearly equal prices (``rolling.vwap_deviation2``
    over double-f32 window sums of the exact ``close * volume``
    products): 0 before the window is full and where it holds no
    volume. The fused kernels' prep and the streaming head evaluate this
    one definition. ``window`` may be traced."""
    pv = rolling.prefix_prod2(close, volume)
    v = rolling.prefix_sum2(volume)
    stacked = jnp.stack([*pv, *v])
    lag = rolling._shifted(stacked, window)
    dev = rolling.vwap_deviation2(
        close, rolling.window_sum2(stacked[0], stacked[1], lag[0], lag[1]),
        rolling.window_sum2(stacked[2], stacked[3], lag[2], lag[3]), eps)
    return jnp.where(rolling.valid_mask(close.shape[-1], window), dev, 0.0)


def _positions(ohlcv, params):
    close, volume = ohlcv.close, ohlcv.volume
    w = params["window"]
    dev = vwap_deviation(close, volume, w)
    z = rolling.rolling_zscore(dev, w, fill=0.0)
    # VWAP needs `w` bars, its deviation's z-score another `w`.
    valid = rolling.valid_mask(close.shape[-1], 2 * jnp.asarray(w) - 1)
    return signals.band_hysteresis_assoc(
        jnp.where(valid, z, 0.0), valid, params["k"], 0.0)


VWAP_REVERSION = register(Strategy(
    name="vwap_reversion",
    param_fields=("window", "k"),
    positions_fn=_positions,
    stateful=True,
))
