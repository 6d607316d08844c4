"""A configuration's market data and instruments, made from the seed.

Daily log returns follow a one-factor model with sectors: each ticker
loads on the market with its own beta, moves with its sector, and has
idiosyncratic noise of its own size. Prices start at a lognormal level.
The same seed gives the same universe; every seed gives the same shapes
(tickers, bars, pairs), so the work of a run does not depend on it.
"""

from __future__ import annotations

import numpy as np

# Used where a configuration leaves its price model out (tests).
DEFAULT_PRICES = {
    "drift_per_year": 0.07, "market_vol_per_year": 0.16,
    "sectors": 11, "sector_vol_per_year": 0.12,
    "idio_vol_median_per_year": 0.22, "idio_vol_log_sd": 0.4,
    "beta_mean": 1.0, "beta_sd": 0.25, "start_median": 60.0,
    "start_log_sd": 0.8,
}


def rng_for(seed: int, stream: str) -> np.random.Generator:
    key = [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
           *(ord(ch) for ch in stream)]
    return np.random.default_rng(np.random.SeedSequence(key))


def _sectors(cfg: dict, seed: int) -> np.ndarray:
    n = int(cfg["instruments"]["tickers"])
    k = int(cfg.get("prices", DEFAULT_PRICES)["sectors"])
    return rng_for(seed, "sector").integers(0, k, n)


def closes(cfg: dict, seed: int) -> np.ndarray:
    """``(tickers, bars)`` float32 closes of the configuration's universe."""
    p = {**DEFAULT_PRICES, **cfg.get("prices", {})}
    n = int(cfg["instruments"]["tickers"])
    T = int(cfg["bars"])
    dt = 1.0 / float(cfg["periods_per_year"])
    rng = rng_for(seed, "prices")
    sector = _sectors(cfg, seed)
    beta = np.clip(rng.normal(p["beta_mean"], p["beta_sd"], n), 0.3, 2.0)
    idio = p["idio_vol_median_per_year"] * np.exp(
        rng.normal(0.0, p["idio_vol_log_sd"], n))
    market = rng.standard_normal(T) * p["market_vol_per_year"]
    sect = rng.standard_normal((int(p["sectors"]), T)) \
        * p["sector_vol_per_year"]
    eps = rng.standard_normal((n, T)) * idio[:, None]
    vol = np.sqrt(beta ** 2 * p["market_vol_per_year"] ** 2
                  + p["sector_vol_per_year"] ** 2 + idio ** 2)
    log_ret = ((p["drift_per_year"] - 0.5 * vol ** 2)[:, None] * dt
               + np.sqrt(dt) * (beta[:, None] * market[None, :]
                                + sect[sector] + eps))
    s0 = p["start_median"] * np.exp(rng.normal(0.0, p["start_log_sd"], n))
    return (s0[:, None] * np.exp(np.cumsum(log_ret, axis=1))).astype(
        np.float32)


def ohlcv(cfg: dict, seed: int, close: np.ndarray) -> list[dict]:
    """Per ticker, the five OHLCV fields (float32) around ``close``: the
    open is the previous close, the wicks and volume are drawn."""
    rng = rng_for(seed, "ohlcv")
    n, T = close.shape
    prev = np.concatenate([close[:, :1], close[:, :-1]], axis=1)
    wick = np.abs(rng.standard_normal((2, n, T))) * 0.005
    high = np.maximum(prev, close) * (1.0 + wick[0])
    low = np.minimum(prev, close) * (1.0 - wick[1])
    volume = np.exp(rng.normal(14.0, 0.7, (n, T)))
    return [{"open": prev[i].astype(np.float32),
             "high": high[i].astype(np.float32),
             "low": low[i].astype(np.float32),
             "close": close[i],
             "volume": volume[i].astype(np.float32)} for i in range(n)]


def instruments(cfg: dict, seed: int) -> list[tuple[int, ...]]:
    """The configuration's instruments as tuples of ticker indices: one
    per ticker, or ``pairs`` pairs whose legs share a sector (as a desk
    pairs them), drawn from the universe so legs repeat."""
    n = int(cfg["instruments"]["tickers"])
    n_pairs = int(cfg["instruments"].get("pairs", 0))
    if not n_pairs:
        return [(i,) for i in range(n)]
    sector = _sectors(cfg, seed)
    rng = rng_for(seed, "pairs")
    if n_pairs > n * (n - 1):
        raise ValueError(f"{n_pairs} pairs from {n} tickers")
    members = {s: np.flatnonzero(sector == s) for s in np.unique(sector)}
    everyone = np.arange(n)
    seen, out, misses = set(), [], 0
    while len(out) < n_pairs:
        y = int(rng.integers(n))
        peers = members[sector[y]]
        if peers.size < 2 or misses > 100:
            # A sector too small (or used up) for another pair.
            peers = everyone
        x = int(peers[rng.integers(peers.size)])
        if x == y or (y, x) in seen:
            misses += 1
            continue
        misses = 0
        seen.add((y, x))
        out.append((y, x))
    return out
