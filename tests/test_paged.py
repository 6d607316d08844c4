"""Ragged paged panel batching (round 10): pool, kernels, backend routing.

Numerics contract under test (DESIGN.md "Ragged paged panels"):

- a UNIFORM group through the paged path is bit-identical to the dense
  fused sweep (the assembled block is the same f32 bits, the same kernel
  runs) — under BOTH ``DBX_EPILOGUE`` substrates;
- a RAGGED group is bit-identical to the dense repeat-last ragged stack,
  and matches per-job unpadded sweeps within the documented
  repeat-last-pad tolerance;
- an append-extended digest (PR 6 chains) reuses all of its base's full
  pages: pool bytes grow O(ΔT/page), not O(T), and the appended sweep
  bit-matches the dense path.

All tests run in-process on tiny shapes (CPU interpret mode) with
explicit PagePool bounds — no subprocesses, no fresh-jax processes (the
tier-1 budget rule). The full 13-family parity loop is ``slow``; the
flagship SMA + the bit-exact band machine stay tier-1.
"""

import numpy as np
import pytest

from distributed_backtesting_exploration_tpu import obs
from distributed_backtesting_exploration_tpu.ops import fused
from distributed_backtesting_exploration_tpu.ops.metrics import Metrics
from distributed_backtesting_exploration_tpu.parallel import sweep
from distributed_backtesting_exploration_tpu.rpc import (
    backtesting_pb2 as pb, compute, wire)
from distributed_backtesting_exploration_tpu.rpc.page_pool import (
    PagePool, page_key, paginate)
from distributed_backtesting_exploration_tpu.rpc.panel_store import (
    panel_digest)
from distributed_backtesting_exploration_tpu.utils import data

B = 16   # test page size (bars); a multiple of 8, small enough that tiny
         # panels span several pages


def _series(t: int, seed: int) -> data.OHLCV:
    panel = data.synthetic_ohlcv(1, t, seed=seed)
    return data.OHLCV(*(np.asarray(f)[0] for f in panel))


def _pool_for(series_list, fields, digests=None, **kw):
    pool = PagePool(page_bars=B, registry=obs.Registry(), **kw)
    digests = digests or [f"d{i}" for i in range(len(series_list))]
    prep = pool.prepare(digests, series_list, fields)
    assert prep is not None
    return pool, prep


SMA_GRID = {k: np.asarray(v) for k, v in sweep.product_grid(
    fast=np.asarray([2.0, 3.0]), slow=np.asarray([8.0, 13.0])).items()}
BOLL_GRID = {k: np.asarray(v) for k, v in sweep.product_grid(
    window=np.asarray([4.0, 6.0]), k=np.asarray([0.5, 1.0])).items()}


def _assert_bit_equal(got: Metrics, want: Metrics, what: str):
    for name, a, b in zip(Metrics._fields, want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (what, name)


@pytest.mark.parametrize("epilogue", ["scan:8", "ladder"])
def test_paged_uniform_bit_identical_sma(epilogue):
    series = [_series(52, seed=i) for i in range(3)]
    _, (pool_arr, tables, _) = _pool_for(series, ("close",))
    dense = fused.fused_sma_sweep(
        np.stack([np.asarray(s.close) for s in series]),
        SMA_GRID["fast"], SMA_GRID["slow"], cost=1e-3, epilogue=epilogue)
    paged = fused.fused_paged_sweep(
        "sma_crossover", pool_arr, tables, [52, 52, 52], SMA_GRID,
        cost=1e-3, epilogue=epilogue)
    _assert_bit_equal(paged, dense, f"sma@{epilogue}")


@pytest.mark.parametrize("epilogue", ["scan:8", "ladder"])
def test_paged_uniform_bit_identical_bollinger(epilogue):
    # The band machine: compose path is selection-only, so the paged
    # twin must be bit-exact on every backend under both substrates.
    series = [_series(48, seed=10 + i) for i in range(2)]
    _, (pool_arr, tables, _) = _pool_for(series, ("close",))
    dense = fused.fused_bollinger_sweep(
        np.stack([np.asarray(s.close) for s in series]),
        BOLL_GRID["window"], BOLL_GRID["k"], cost=1e-3, epilogue=epilogue)
    paged = fused.fused_paged_sweep(
        "bollinger", pool_arr, tables, [48, 48], BOLL_GRID,
        cost=1e-3, epilogue=epilogue)
    _assert_bit_equal(paged, dense, f"bollinger@{epilogue}")


@pytest.mark.parametrize("epilogue", ["scan:8", "ladder"])
def test_paged_ragged_bit_identical_to_dense_ragged(epilogue):
    # Mixed lengths, same page count (one bin) AND different page counts
    # (two bins): either way the assembled block must equal the dense
    # repeat-last ragged stack bit-for-bit, and so must the metrics.
    lens = [52, 41, 23]    # pages 4, 3, 2 at B=16 -> three bins
    series = [_series(52, seed=20 + i) for i in range(3)]
    series = [data.OHLCV(*(np.asarray(f)[:t] for f in s))
              for s, t in zip(series, lens)]
    _, (pool_arr, tables, _) = _pool_for(series, ("close",))
    paged = fused.fused_paged_sweep(
        "sma_crossover", pool_arr, tables, lens, SMA_GRID, cost=1e-3,
        epilogue=epilogue)
    # Dense ragged reference PER PAGE-COUNT BIN: the paged schedule pads
    # each ticker only to its own bin max, so the bit-exact twin is the
    # dense ragged stack of that bin (globally it is the repeat-last
    # contract, asserted in the tolerance test below).
    for idx in ([0], [1], [2]):
        stack = compute._stack_field_ragged(
            [series[i] for i in idx], max(lens[i] for i in idx))
        t_real = (None if len({lens[i] for i in idx}) == 1
                  and stack.shape[1] == lens[idx[0]] else
                  np.asarray([lens[i] for i in idx], np.int32))
        dense = fused.fused_sma_sweep(
            stack, SMA_GRID["fast"], SMA_GRID["slow"], cost=1e-3,
            t_real=t_real, epilogue=epilogue)
        for name, a, b in zip(Metrics._fields, dense, paged):
            got = np.asarray(b)[np.asarray(idx)]
            assert np.array_equal(got, np.asarray(a)), (name, idx)


def test_paged_ragged_repeat_last_contract():
    # vs per-job UNPADDED sweeps: the documented repeat-last-pad contract
    # (pad bars earn zero and hold the last position) within f32
    # association tolerance — for the flagship and a band machine.
    lens = [52, 37, 29]
    series = [data.OHLCV(*(np.asarray(f)[:t] for f in _series(52, 30 + i)))
              for i, t in enumerate(lens)]
    _, (pool_arr, tables, _) = _pool_for(series, ("close",))
    for strategy, grid in (("sma_crossover", SMA_GRID),
                           ("bollinger", BOLL_GRID)):
        paged = fused.fused_paged_sweep(
            strategy, pool_arr, tables, lens, grid, cost=1e-3)
        _, _, call = fused._PAGED_FAMILIES[strategy]
        for i, s in enumerate(series):
            ref = call([np.asarray(s.close)[None, :]], grid,
                       t_real=None, cost=1e-3, periods_per_year=252,
                       interpret=True, epilogue=None)
            for name, a, b in zip(Metrics._fields, ref, paged):
                np.testing.assert_allclose(
                    np.asarray(b)[i], np.asarray(a)[0], rtol=2e-5,
                    atol=2e-6, err_msg=f"{strategy}:{name}:job{i}")


@pytest.mark.slow
@pytest.mark.parametrize("strategy", sorted(fused._PAGED_FAMILIES))
def test_paged_parity_all_families(strategy):
    # Full-family paged-vs-dense twins (ragged, both substrates) — the
    # tier-1 gate keeps the flagship + band machine; this loop is the
    # exhaustive slow twin.
    fields, axes, call = fused._PAGED_FAMILIES[strategy]
    vals = {"fast": [2.0, 3.0], "slow": [8.0, 13.0], "window": [3.0, 5.0],
            "k": [0.5, 1.0], "lookback": [2.0, 4.0], "period": [3.0, 5.0],
            "band": [10.0, 20.0], "signal": [2.0, 3.0], "span": [2.0, 3.0]}
    grid = {a: np.asarray(v) for a, v in sweep.product_grid(
        **{a: np.asarray(vals[a], np.float32) for a in axes}).items()}
    lens = [52, 41]
    series = [data.OHLCV(*(np.asarray(f)[:t] for f in _series(52, 40 + i)))
              for i, t in enumerate(lens)]
    _, (pool_arr, tables, _) = _pool_for(series, fields)
    for epilogue in ("scan:8", "ladder"):
        paged = fused.fused_paged_sweep(
            strategy, pool_arr, tables, lens, grid, cost=1e-3,
            epilogue=epilogue)
        for idx in ([0], [1]):
            t_bin = [lens[i] for i in idx]
            arrays = [compute._stack_field_ragged(
                [series[i] for i in idx], max(t_bin), f) for f in fields]
            dense = call(arrays, grid, t_real=None, cost=1e-3,
                         periods_per_year=252, interpret=True,
                         epilogue=epilogue)
            for name, a, b in zip(Metrics._fields, dense, paged):
                assert np.array_equal(np.asarray(b)[np.asarray(idx)],
                                      np.asarray(a)), \
                    (strategy, epilogue, name)


def test_append_chain_shares_base_pages():
    # Satellite: an append-extended digest (PR 6 chain) reuses all of its
    # base's FULL pages — pool bytes grow by O(ΔT/page) + the boundary
    # page, never O(T).
    t_base, dt = 7 * B + 5, 9      # partial boundary page + small delta
    base = _series(t_base + dt, seed=7)
    base_panel = data.OHLCV(*(np.asarray(f)[:t_base] for f in base))
    ext_panel = data.OHLCV(*(np.asarray(f)[:t_base + dt] for f in base))
    pool = PagePool(page_bars=B, registry=obs.Registry())
    prep = pool.prepare(["base"], [base_panel], ("close",))
    assert prep is not None
    pages_base = pool.stats()["pages"]
    bytes_base = pool.stats()["bytes"]
    assert pages_base == -(-t_base // B)
    prep2 = pool.prepare(["ext"], [ext_panel], ("close",))
    assert prep2 is not None
    added = pool.stats()["pages"] - pages_base
    # ΔT=9 with a partial boundary page: the boundary page's content
    # changed (pad -> real bars) and no new page index is needed, so
    # exactly one page uploads; never more than ceil(ΔT/B) + 1.
    assert added <= -(-dt // B) + 1, added
    assert pool.stats()["bytes"] - bytes_base == added * B * 4
    # The appended sweep bit-matches the dense path (the PR 3/6 contract:
    # same kernel, same assembled bits).
    pool_arr, tables, _ = prep2
    paged = fused.fused_paged_sweep(
        "sma_crossover", pool_arr, tables, [t_base + dt], SMA_GRID,
        cost=1e-3)
    dense = fused.fused_sma_sweep(
        np.asarray(ext_panel.close)[None, :], SMA_GRID["fast"],
        SMA_GRID["slow"], cost=1e-3)
    _assert_bit_equal(paged, dense, "append-chain")


def test_overlapping_histories_share_pages_across_digests():
    # Content keying: two DIFFERENT digests whose histories share a
    # full-page-aligned prefix share those pages — device bytes sublinear
    # in ticker count for overlapping histories.
    s = _series(6 * B, seed=9)
    a = data.OHLCV(*(np.asarray(f)[:5 * B] for f in s))
    b = data.OHLCV(*(np.asarray(f)[:6 * B] for f in s))
    pool = PagePool(page_bars=B, registry=obs.Registry())
    assert pool.prepare(["da"], [a], ("close",)) is not None
    before = pool.stats()["pages"]
    assert pool.prepare(["db"], [b], ("close",)) is not None
    assert pool.stats()["pages"] - before == 1   # only the new tail page


def test_pool_bounds_eviction_and_reject():
    reg = obs.Registry()
    pool = PagePool(page_bars=B, max_bytes=4 * B * 4, registry=reg)
    assert pool.capacity == 4
    s1 = _series(3 * B, seed=1)
    assert pool.prepare(["d1"], [s1], ("close",)) is not None
    assert pool.stats()["pages"] == 3
    # A second 3-page panel fits only by evicting LRU pages of the first.
    s2 = _series(3 * B, seed=2)
    assert pool.prepare(["d2"], [s2], ("close",)) is not None
    assert pool.stats()["pages"] <= 4
    assert pool.stats()["bytes"] <= pool.max_bytes
    # A group larger than the whole pool is REJECTED, not thrashed.
    s3 = _series(6 * B, seed=3)
    assert pool.prepare(["d3"], [s3], ("close",)) is None
    assert reg.counter("dbx_page_pool_rejects_total").value >= 1


def test_pool_counters_and_gauges():
    reg = obs.Registry()
    pool = PagePool(page_bars=B, registry=reg)
    s = _series(2 * B + 3, seed=4)
    assert pool.prepare(["d"], [s], ("close",)) is not None
    assert reg.counter("dbx_page_pool_misses_total", field="close").value \
        == 3
    assert pool.prepare(["d"], [s], ("close",)) is not None   # warm
    assert reg.counter("dbx_page_pool_hits_total", field="close").value \
        == 3
    assert reg.gauge("dbx_page_pool_pages").value == 3
    assert reg.gauge("dbx_page_pool_bytes").value == 3 * B * 4


def _specs(series_list, grid, strategy="sma_crossover", cost=1e-3):
    out = []
    for i, s in enumerate(series_list):
        raw = data.to_wire_bytes(s)
        out.append(pb.JobSpec(
            id=f"j{i}", strategy=strategy, ohlcv=raw,
            panel_digest=panel_digest(raw), grid=wire.grid_to_proto(grid),
            cost=cost, periods_per_year=252))
    return out


def _backend(monkeypatch, **kw):
    monkeypatch.setenv("DBX_PAGE_BARS", str(B))
    monkeypatch.setenv("DBX_PAGE_POOL_MB", "4")
    return compute.JaxSweepBackend(use_fused=True, use_mesh=False, **kw)


def test_backend_mixed_lengths_fuse_and_route_paged(monkeypatch):
    be = _backend(monkeypatch)
    assert be.use_paged
    lens = (64, 41, 52, 64)
    series = [data.OHLCV(*(np.asarray(f)[:t]
                           for f in _series(64, 50 + i)))
              for i, t in enumerate(lens)]
    axes = {"fast": np.asarray([2.0, 3.0]), "slow": np.asarray([8.0])}
    specs = _specs(series, axes)
    # One submit group despite four lengths: the paged key drops the
    # length bucket entirely.
    assert len({be._length_bucket(j, axes) for j in specs}) == 1
    comps = {c.job_id: c for c in be.process(specs)}
    assert len(comps) == 4 and all(c.metrics for c in comps.values())
    prod = {k: np.asarray(v)
            for k, v in sweep.product_grid(**axes).items()}
    for i, s in enumerate(series):
        ref = fused.fused_sma_sweep(
            np.asarray(s.close)[None, :], prod["fast"], prod["slow"],
            cost=1e-3)
        got = wire.metrics_from_bytes(comps[f"j{i}"].metrics)
        np.testing.assert_allclose(
            np.asarray(got.sharpe).ravel(),
            np.asarray(ref.sharpe).ravel(), rtol=2e-5, atol=2e-6)
    # Pool observability advanced: pages resident, misses counted, the
    # partial tail pages' pad accounted to the paged path.
    st = be.panel_cache.stats()["page_pool"]
    assert st["pages"] > 0 and st["bytes"] > 0
    reg = obs.get_registry()
    assert reg.counter("dbx_page_pool_misses_total", field="close").value \
        > 0
    assert reg.counter("dbx_pad_bars_total", path="paged").value > 0
    # Warm re-submit: every page hits, nothing uploads, and the pending
    # entry's h2d-hit flag (collect's d2h span cache_hit attr) reports
    # the pool-warm state like a device-block hit.
    misses = reg.counter("dbx_page_pool_misses_total", field="close").value
    pend = be.submit(_specs(series, axes))
    assert len(pend) == 1 and pend[0][5] is True
    be.collect(pend)
    assert reg.counter("dbx_page_pool_misses_total",
                       field="close").value == misses
    assert reg.counter("dbx_page_pool_hits_total", field="close").value > 0


def test_backend_over_cap_ragged_splits_through_paging(monkeypatch):
    # The generic-path demotion for over-VMEM-cap ragged groups routes
    # through paging first: only the genuinely-long member demotes, the
    # under-cap members keep the fused (paged) route. The cap is a class
    # attr — shrink it so the "long" panel stays test-sized.
    monkeypatch.setattr(compute.JaxSweepBackend, "_FUSED_MAX_BARS", 64)
    be = _backend(monkeypatch)
    lens = (48, 96, 33)
    series = [data.OHLCV(*(np.asarray(f)[:t]
                           for f in _series(96, 70 + i)))
              for i, t in enumerate(lens)]
    axes = {"fast": np.asarray([2.0]), "slow": np.asarray([8.0])}
    specs = _specs(series, axes)
    # No length buckets -> one merged group whose t_max breaks the cap.
    assert len({be._length_bucket(j, axes) for j in specs}) == 1
    comps = {c.job_id: c for c in be.process(specs)}
    assert len(comps) == 3 and all(c.metrics for c in comps.values())
    for i, s in enumerate(series):
        ref = fused.fused_sma_sweep(
            np.asarray(s.close)[None, :], axes["fast"], axes["slow"],
            cost=1e-3)
        got = wire.metrics_from_bytes(comps[f"j{i}"].metrics)
        np.testing.assert_allclose(
            np.asarray(got.sharpe).ravel(),
            np.asarray(ref.sharpe).ravel(), rtol=2e-5, atol=2e-6)
    # The under-cap members went through the pool (pages resident for
    # the 48- and 33-bar panels: 3 + 3 pages at B=16), the 96-bar panel
    # stayed off it.
    st = be.panel_cache.stats()["page_pool"]
    assert st["pages"] == -(-48 // B) + -(-33 // B)


def test_backend_pool_reject_falls_back_dense(monkeypatch):
    # A pool too small for even one group degrades to the dense stacks —
    # jobs still complete, bit-for-bit the same results.
    monkeypatch.setenv("DBX_PAGE_BARS", str(B))
    monkeypatch.setenv("DBX_PAGE_POOL_MB",
                       str(2 * B * 4 / (1024 * 1024)))   # 2 slots
    be = compute.JaxSweepBackend(use_fused=True, use_mesh=False)
    series = [data.OHLCV(*(np.asarray(f)[:t]
                           for f in _series(64, 60 + i)))
              for i, t in enumerate((64, 41))]
    axes = {"fast": np.asarray([2.0]), "slow": np.asarray([8.0])}
    comps = {c.job_id: c for c in be.process(_specs(series, axes))}
    assert len(comps) == 2 and all(c.metrics for c in comps.values())
    for i, s in enumerate(series):
        ref = fused.fused_sma_sweep(
            np.asarray(s.close)[None, :], axes["fast"], axes["slow"],
            cost=1e-3)
        got = wire.metrics_from_bytes(comps[f"j{i}"].metrics)
        np.testing.assert_allclose(
            np.asarray(got.sharpe).ravel(),
            np.asarray(ref.sharpe).ravel(), rtol=2e-5, atol=2e-6)


def test_paged_kill_switch_and_knob_validation(monkeypatch):
    axes = {"fast": np.asarray([2.0]), "slow": np.asarray([8.0])}
    monkeypatch.setenv("DBX_PAGED", "0")
    be = compute.JaxSweepBackend(use_fused=True, use_mesh=False)
    assert not be.use_paged
    job = pb.JobSpec(strategy="sma_crossover", wf_train=0,
                     panel_digest="d" * 32, panel_bytes_len=1000)
    assert be._length_bucket(job, axes) == (1000).bit_length()
    monkeypatch.delenv("DBX_PAGED")
    be2 = compute.JaxSweepBackend(use_fused=True, use_mesh=False)
    assert be2.use_paged and be2._length_bucket(job, axes) == 0
    # wf/pairs/best_returns jobs keep the bucket even when paged is live,
    # as do digestless jobs (they cannot take the paged route, and one
    # of them must not drag a merged group onto the dense fallback) and
    # jobs whose grid fails the length-independent fused gates.
    wf = pb.JobSpec(strategy="sma_crossover", wf_train=10,
                    panel_digest="d" * 32, panel_bytes_len=1000)
    assert be2._length_bucket(wf, axes) == (1000).bit_length()
    nodigest = pb.JobSpec(strategy="sma_crossover", panel_bytes_len=1000)
    assert be2._length_bucket(nodigest, axes) == (1000).bit_length()
    bad_grid = {"fast": np.asarray([2.5]), "slow": np.asarray([8.0])}
    assert be2._length_bucket(job, bad_grid) == (1000).bit_length()
    for bad in ("x", "-8", "12"):
        monkeypatch.setenv("DBX_PAGE_BARS", bad)
        with pytest.raises(ValueError):
            fused.resolve_page_bars()
    monkeypatch.setenv("DBX_PAGE_BARS", "64")
    assert fused.resolve_page_bars() == 64


@pytest.mark.parametrize("strategy", ["bollinger", "bollinger_touch",
                                      "vwap_reversion"])
def test_band_families_never_batch_ragged(monkeypatch, strategy):
    # ROADMAP B7: a ragged group changes a band family's rolling-variance
    # rounding (on the v5e, up to 5 % of Sharpe cells), so these families
    # group by exact length: each job's result is bit-identical to the
    # same job run alone, whatever it was batched with.
    be = _backend(monkeypatch)
    series = [data.OHLCV(*(np.asarray(f)[:t]
                           for f in _series(64, 70 + i)))
              for i, t in enumerate((64, 41, 64))]
    specs = _specs(series, BOLL_GRID, strategy=strategy)
    assert [be._length_bucket(s, BOLL_GRID) for s in specs] == [
        -len(s.ohlcv) for s in specs]
    batched = {c.job_id: c.metrics for c in be.process(specs)}
    for spec in specs:
        (alone,) = _backend(monkeypatch).process([spec])
        assert alone.metrics == batched[spec.id], spec.id
    pairs = pb.JobSpec(strategy="pairs", panel_digest="d" * 32,
                       panel_bytes_len=1000)
    assert be._length_bucket(pairs, {}) == -1000


def test_groups_split_to_the_device_memory_budget(monkeypatch):
    # A group the size of a worker's lease is cut into launches that each
    # fit the per-chip HBM budget; the cut changes no result.
    be = _backend(monkeypatch)
    specs = _specs([_series(64, 80 + i) for i in range(5)], SMA_GRID)
    combos = np.prod([len(v) for v in
                      wire.grid_from_proto(specs[0].grid).values()])
    per_job = (128 * be._HBM_BYTES_PER_BAR
               + combos * be._HBM_BYTES_PER_COMBO)
    assert be._group_limit(specs) == be._HBM_BUDGET // per_job
    monkeypatch.setattr(be, "_HBM_BUDGET", 2 * per_job)
    assert be._group_limit(specs) == 2
    finish, launched = be._finish_group, []

    def counting_finish(group, *a, **k):
        launched.append(len(group))
        return finish(group, *a, **k)

    monkeypatch.setattr(be, "_finish_group", counting_finish)
    split = {c.job_id: c.metrics for c in be.process(specs)}
    assert launched == [2, 2, 1]
    whole = {c.job_id: c.metrics for c in _backend(monkeypatch).process(specs)}
    assert split == whole


def test_paged_fields_match_fused_registry():
    # ONE source of truth: the worker prepares page tables from
    # fused.paged_fields, and the two registries' field tuples AND grid
    # axes must agree for every family (a drift would raise mid-submit,
    # or misbuild the hygiene probe's grid).
    for strategy, spec in compute.JaxSweepBackend._FUSED_STRATEGIES.items():
        assert fused.paged_fields(strategy) == spec.fields, strategy
        _, axes, _ = fused._PAGED_FAMILIES[strategy]
        assert set(axes) == spec.axes, strategy


def test_backend_pool_reject_resplits_mixed_group(monkeypatch):
    # A pool-rejected MERGED mixed-length group re-splits by the pre-
    # paging power-of-two bucket before stacking densely — the ~2x pad
    # bound survives the fallback (jobs complete, two dense groups).
    monkeypatch.setenv("DBX_PAGE_BARS", str(B))
    monkeypatch.setenv("DBX_PAGE_POOL_MB",
                       str(1 * B * 4 / (1024 * 1024)))    # 1 slot: reject
    be = compute.JaxSweepBackend(use_fused=True, use_mesh=False)
    series = [data.OHLCV(*(np.asarray(f)[:t]
                           for f in _series(256, 80 + i)))
              for i, t in enumerate((256, 48))]    # different pow2 buckets
    axes = {"fast": np.asarray([2.0]), "slow": np.asarray([8.0])}
    reg = obs.get_registry()
    pad0 = reg.counter("dbx_pad_bars_total", path="dense").value
    comps = {c.job_id: c for c in be.process(_specs(series, axes))}
    assert len(comps) == 2 and all(c.metrics for c in comps.values())
    # Re-split means NO cross-bucket padding: the dense pad counter must
    # not have been charged 256-48 bars for the short job.
    assert reg.counter("dbx_pad_bars_total",
                       path="dense").value - pad0 == 0
    for i, s in enumerate(series):
        ref = fused.fused_sma_sweep(
            np.asarray(s.close)[None, :], axes["fast"], axes["slow"],
            cost=1e-3)
        got = wire.metrics_from_bytes(comps[f"j{i}"].metrics)
        np.testing.assert_allclose(
            np.asarray(got.sharpe).ravel(),
            np.asarray(ref.sharpe).ravel(), rtol=2e-5, atol=2e-6)


def test_page_key_and_paginate_canonical():
    v = np.arange(B + 3, dtype=np.float32)
    pages = paginate(v, B)
    assert len(pages) == 2 and pages[1].shape == (B,)
    # repeat-last pad inside the partial page is canonical content.
    assert np.all(pages[1][3:] == v[-1])
    assert page_key(pages[0].tobytes()) != page_key(pages[1].tobytes())
    # full-page prefix of a longer series hashes identically (the
    # sharing property the append-chain test exercises end to end).
    w = np.arange(2 * B, dtype=np.float32)
    assert page_key(paginate(w, B)[0].tobytes()) == \
        page_key(pages[0].tobytes())


def test_paged_hygiene_probe_traces(monkeypatch):
    # The lint gate runs the full registry; this pins the probe contract
    # itself (tier-1-cheap: one family, both substrates) and the loud
    # failure for unregistered strategies.
    import jax

    for epi in ("scan:8", "ladder"):
        monkeypatch.setenv("DBX_EPILOGUE", epi)
        fn, args = fused.paged_hygiene_probe("sma_crossover")
        jaxpr = jax.make_jaxpr(fn)(*args)
        assert jaxpr.out_avals   # traced through gather + kernel
    with pytest.raises(KeyError):
        fused.paged_hygiene_probe("no_such_family")
