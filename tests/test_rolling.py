"""Golden tests: rolling ops vs trivially-correct float64 NumPy loops."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_backtesting_exploration_tpu.ops import rolling


RNG = np.random.default_rng(42)
T = 400
# Price-like levels: the numerically nasty case for f32 second moments.
X = (100.0 * np.exp(np.cumsum(RNG.normal(0, 0.02, T)))).astype(np.float64)
Y = (80.0 * np.exp(np.cumsum(RNG.normal(0, 0.02, T)))).astype(np.float64)


def np_rolling(x, w, fn):
    out = np.full_like(x, np.nan)
    for t in range(w - 1, len(x)):
        out[t] = fn(x[t - w + 1: t + 1])
    return out


@pytest.mark.parametrize("w", [2, 5, 20, 128])
def test_rolling_mean(w):
    got = np.asarray(rolling.rolling_mean(jnp.asarray(X, jnp.float32), w))
    want = np_rolling(X, w, np.mean)
    np.testing.assert_allclose(got[w - 1:], want[w - 1:], rtol=1e-4)
    assert np.isnan(got[: w - 1]).all()


@pytest.mark.parametrize("w,ddof", [(5, 0), (20, 0), (20, 1), (64, 1)])
def test_rolling_std(w, ddof):
    got = np.asarray(rolling.rolling_std(jnp.asarray(X, jnp.float32), w, ddof=ddof))
    want = np_rolling(X, w, lambda s: np.std(s, ddof=ddof))
    np.testing.assert_allclose(got[w - 1:], want[w - 1:], rtol=5e-3, atol=1e-4)


@pytest.mark.parametrize("w", [5, 30])
def test_rolling_zscore(w):
    got = np.asarray(rolling.rolling_zscore(jnp.asarray(X, jnp.float32), w))
    m = np_rolling(X, w, np.mean)
    s = np_rolling(X, w, np.std)
    want = (X - m) / s
    np.testing.assert_allclose(got[w - 1:], want[w - 1:], rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("w", [10, 60])
def test_rolling_ols(w):
    alpha, beta = rolling.rolling_ols(
        jnp.asarray(Y, jnp.float32), jnp.asarray(X, jnp.float32), w)
    alpha, beta = np.asarray(alpha), np.asarray(beta)
    want_a = np.full(T, np.nan)
    want_b = np.full(T, np.nan)
    for t in range(w - 1, T):
        xs, ys = X[t - w + 1: t + 1], Y[t - w + 1: t + 1]
        b, a = np.polyfit(xs, ys, 1)
        want_a[t], want_b[t] = a, b
    np.testing.assert_allclose(beta[w - 1:], want_b[w - 1:], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(alpha[w - 1:], want_a[w - 1:], rtol=2e-2, atol=2.0)


@pytest.mark.parametrize("span", [3, 21])
def test_ema(span):
    got = np.asarray(rolling.ema(jnp.asarray(X, jnp.float32), span=span))
    a = 2.0 / (span + 1)
    want = np.empty_like(X)
    want[0] = X[0]
    for t in range(1, T):
        want[t] = (1 - a) * want[t - 1] + a * X[t]
    np.testing.assert_allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("w", [1, 2, 7, 33])
def test_rolling_max_min(w):
    gmax = np.asarray(rolling.rolling_max(jnp.asarray(X, jnp.float32), w))
    gmin = np.asarray(rolling.rolling_min(jnp.asarray(X, jnp.float32), w))
    np.testing.assert_allclose(gmax[w - 1:], np_rolling(X, w, np.max)[w - 1:],
                               rtol=1e-6)
    np.testing.assert_allclose(gmin[w - 1:], np_rolling(X, w, np.min)[w - 1:],
                               rtol=1e-6)


def test_traced_window_vmap_matches_static():
    """vmap over a window grid must equal per-window static calls."""
    x = jnp.asarray(X, jnp.float32)
    windows = jnp.asarray([3, 10, 50], jnp.int32)
    batched = jax.vmap(lambda w: rolling.rolling_mean(x, w, fill=0.0))(windows)
    for i, w in enumerate([3, 10, 50]):
        single = rolling.rolling_mean(x, w, fill=0.0)
        np.testing.assert_allclose(np.asarray(batched[i]), np.asarray(single),
                                   rtol=1e-6)


def test_rolling_sum_under_jit():
    x = jnp.asarray(X, jnp.float32)
    f = jax.jit(lambda x, w: rolling.rolling_sum(x, w, fill=0.0))
    np.testing.assert_allclose(
        np.asarray(f(x, 7)),
        np.asarray(rolling.rolling_sum(x, 7, fill=0.0)), rtol=1e-6)


# --- compensated window sums and the double-f32 OLS (PR 21) ---------------

LONG = (100.0 * np.exp(np.cumsum(RNG.normal(0, 0.02, 8192)))).astype(
    np.float32)


@pytest.mark.parametrize("w", [5, 50, 200])
def test_rolling_sum_is_exact_to_the_window_on_long_histories(w):
    # A plain f32 cumsum difference carries the whole prefix's rounding
    # (~1e-4 of the window sum at 8,192 price-level bars); the compensated
    # pair keeps each window sum within a few ulps of itself.
    got = np.asarray(rolling.rolling_sum(jnp.asarray(LONG), w))
    x = LONG.astype(np.float64)
    cs = np.cumsum(x)
    want = cs - np.concatenate([np.zeros(w), cs[:-w]])
    np.testing.assert_allclose(got[w - 1:], want[w - 1:], rtol=2e-6)


def test_prefix_sum2_telescopes_whatever_the_scan_order(monkeypatch):
    # The pair's sum is the exact prefix sum even when the backend's scan
    # associates differently: emulate one with a blocked cumsum.
    x = jnp.asarray(LONG)
    hi, lo = rolling.prefix_sum2(x)
    plain = jnp.cumsum

    def blocked(a, axis=-1):
        b = a.reshape(a.shape[:-1] + (-1, 128))
        loc = plain(b, axis=-1)
        off = plain(loc[..., -1], axis=-1) - loc[..., -1]
        return (loc + off[..., None]).reshape(a.shape)

    monkeypatch.setattr(jnp, "cumsum", blocked)
    hi_b, lo_b = rolling.prefix_sum2(x)
    exact = np.cumsum(LONG.astype(np.float64))
    for h, l in ((hi, lo), (hi_b, lo_b)):
        total = np.asarray(h, np.float64) + np.asarray(l, np.float64)
        np.testing.assert_allclose(total, exact, rtol=1e-9)
    assert not np.array_equal(np.asarray(hi), np.asarray(hi_b))


def test_two_prod_is_exact():
    a = RNG.normal(0, 50, 1000).astype(np.float32)
    b = RNG.normal(0, 50, 1000).astype(np.float32)
    p, err = rolling.two_prod(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(p, np.float64) + np.asarray(err, np.float64),
        a.astype(np.float64) * b.astype(np.float64))


@pytest.mark.parametrize("w", [10, 32])
def test_rolling_ols_residual_matches_float64_and_jit(w):
    # beta is formed in double-f32 on the centred legs, so it no longer
    # hangs on how one compilation rounded the cancellation: f32 agrees
    # with float64, and the jitted and eager runs agree with each other.
    y32, x32 = (jnp.asarray(LONG[:1260]),
                jnp.asarray(LONG[2000:3260] * 0.7 + 3.0))
    beta, resid = rolling.rolling_ols_residual(y32, x32, w)
    jbeta, jresid = jax.jit(rolling.rolling_ols_residual,
                            static_argnums=2)(y32, x32, w)
    y, x = np.asarray(y32, np.float64), np.asarray(x32, np.float64)
    want_b = np.full(1260, np.nan)
    want_r = np.full(1260, np.nan)
    for t in range(w - 1, 1260):
        xs, ys = x[t - w + 1: t + 1], y[t - w + 1: t + 1]
        b, a = np.polyfit(xs, ys, 1)
        want_b[t], want_r[t] = b, y[t] - (a + b * x[t])
    scale = np.nanstd(want_r)
    np.testing.assert_allclose(np.asarray(beta)[w - 1:], want_b[w - 1:],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(resid)[w - 1:], want_r[w - 1:],
                               atol=1e-4 * scale)
    # (single-f32 moments put them up to 7.6e-4 apart on the smoke's pairs)
    np.testing.assert_allclose(np.asarray(jbeta), np.asarray(beta),
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(jresid), np.asarray(resid),
                               atol=1e-5 * scale)
