"""Tier-1 smoke for bench.py's roofline_stages scaffold.

The per-stage attribution is the instrument every roofline decision in
DESIGN.md is cut from; a silent bitrot there (stage kernel drifting from
ops/fused.py, a renamed attribution key) would invalidate the next round's
measurements without failing anything. This runs the REAL scaffold
in-process on tiny shapes (CPU interpret mode) and asserts the attribution
keys exist and every stage time is positive — a structure test, not a
performance test.

(Named ``test_z_*`` deliberately: tier-1 runs under a fixed wall budget
that can truncate the alphabetical tail on slow boxes — additions must be
the tests a truncation drops, never the seed suite.)
"""

import contextlib
import io
import json
import os

import pytest

import bench
from distributed_backtesting_exploration_tpu.runtime import (
    _core as native_core)

_TINY_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_TICKERS": "2", "DBX_BENCH_BARS": "64",
    "DBX_BENCH_PARAMS": "8", "DBX_BENCH_ITERS": "1",
    "DBX_BENCH_WARMUP": "0", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "roofline_stages",
}


@pytest.fixture(scope="module")
def roofline():
    """One tiny in-process roofline_stages run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _TINY_ENV}
    prior["DBX_EPILOGUE"] = os.environ.pop("DBX_EPILOGUE", None)
    os.environ.update(_TINY_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


SMA_STAGE_KEYS = (
    "prep_l128_s_per_sweep", "touch_l128_s_per_sweep",
    "matmul_l128_s_per_sweep", "signal_l128_s_per_sweep",
    "no_ladders_l128_s_per_sweep", "full_l128_s_per_sweep",
    "full_ladder_l128_s_per_sweep", "table_hbm_s_per_sweep",
    "table_inline_s_per_sweep", "epilogue_scan_s_per_sweep",
    "epilogue_ladder_s_per_sweep",
)
BOLL_STAGE_KEYS = (
    "prep_l128_s_per_sweep", "touch_l128_s_per_sweep",
    "matmul_l128_s_per_sweep", "signal_l128_s_per_sweep",
    "signal_ladder_l128_s_per_sweep", "no_ladders_l128_s_per_sweep",
    "full_l128_s_per_sweep", "full_ladder_l128_s_per_sweep",
    "epilogue_scan_s_per_sweep", "epilogue_ladder_s_per_sweep",
)
ATTRIBUTION_KEYS = (
    "selection_matmul_pct", "signal_delta_pct", "reductions_delta_pct",
    "ladders_delta_pct", "ladder_fallback_delta_pct",
    "epilogue_scan_speedup", "epilogue_e2e_speedup",
)


def test_sma_stage_attribution_present(roofline):
    stages = roofline["roofline"]["sma_stages"]
    for key in SMA_STAGE_KEYS:
        assert key in stages, key
        assert stages[key] > 0.0, key
    for key in ATTRIBUTION_KEYS + ("inline_table_speedup",):
        assert key in stages, key
    assert stages["epilogue_scan_speedup"] > 0.0
    assert stages["inline_table_speedup"] > 0.0


def test_bollinger_stage_attribution_present(roofline):
    stages = roofline["roofline"]["bollinger_stages"]
    for key in BOLL_STAGE_KEYS:
        assert key in stages, key
        assert stages[key] > 0.0, key
    for key in ATTRIBUTION_KEYS + ("compose_delta_pct",
                                   "compose_ladder_delta_pct"):
        assert key in stages, key


def test_roofline_rates_reported(roofline):
    assert roofline["configs"]["roofline_stages_full"] > 0.0
    assert roofline["configs"]["roofline_stages_boll_full"] > 0.0


_LOCAL_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "e2e_local,direct_dispatch",
    # Tiny-but-real loopback runs: one worker count, few jobs, a small
    # shared panel for the dedupe A/B — structure smoke, not performance.
    "DBX_BENCH_LOCAL_JOBS": "48", "DBX_BENCH_LOCAL_WORKERS": "1",
    "DBX_BENCH_DEDUPE_BARS": "256",
}


@pytest.fixture(scope="module")
def local_bench():
    """One tiny in-process e2e_local + direct_dispatch run (loopback gRPC,
    instant backend — no kernels, no compiles), shared by the module."""
    prior = {k: os.environ.get(k) for k in _LOCAL_ENV}
    os.environ.update(_LOCAL_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_wire_bytes_per_job_keys_present(local_bench):
    """Transport savings are a first-class bench column: e2e_local and
    direct_dispatch_floor both record wire_bytes_per_job, and the dedupe
    A/B records its jobs/s + wire columns (the dispatch-by-digest
    acceptance numbers ride these keys)."""
    e2e = local_bench["roofline"]["e2e_local"]
    assert e2e["wire_bytes_per_job"]["w1"] > 0.0
    dd = e2e["dedupe"]
    for key in ("panel_bytes", "jobs_per_s_on", "jobs_per_s_off",
                "dedupe_speedup", "wire_bytes_per_job_on",
                "wire_bytes_per_job_off", "wire_reduction"):
        assert key in dd, key
    assert dd["jobs_per_s_on"] > 0.0 and dd["jobs_per_s_off"] > 0.0
    # Digest-only dispatch must actually shrink the wire even at smoke
    # scale (the >=10x acceptance bar is asserted on the real-size run).
    assert dd["wire_bytes_per_job_on"] < dd["wire_bytes_per_job_off"]

    floor = local_bench["roofline"]["direct_dispatch_floor"]
    assert floor["wire_bytes_per_job"]["b32"] > 0.0
    assert floor["wire_bytes_per_job"]["b128"] > 0.0


def test_direct_dispatch_lockdep_ab_keys_present(local_bench):
    """Round 12: the direct_dispatch floor is re-measured with the
    runtime lockdep shim on — overhead and violation count are tracked
    bench columns (DBX_LOCKDEP=1 must stay fleet-viable), and the
    instrumented control-plane cycle must be violation-free."""
    ld = local_bench["roofline"]["direct_dispatch_floor"]["lockdep"]
    for key in ("batch32_jobs_per_s", "overhead_pct", "floor_ok",
                "edges", "violations"):
        assert key in ld, key
    assert ld["batch32_jobs_per_s"] > 0.0
    assert ld["violations"] == 0


_STREAM_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "streaming_append",
    # Tiny-but-real in-process A/B: base history past the carry's tail
    # (the partial-tail recurrent head — the serving path), few updates.
    "DBX_BENCH_STREAM_T": "192", "DBX_BENCH_STREAM_DT": "8",
    "DBX_BENCH_ITERS": "2",
}


@pytest.fixture(scope="module")
def stream_bench():
    """One tiny in-process streaming_append run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _STREAM_ENV}
    os.environ.update(_STREAM_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


_CERTIFY_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "certify",
    # Tiny-but-real: two families through the REAL certifier (4 rows
    # each: 2 epilogue substrates x {build_carry, append_step}) plus the
    # digest cones — the analysis cost instrument, not a numerics check
    # (the contract gate itself lives in test_lint_clean.py).
    "DBX_BENCH_CERTIFY_FAMILIES": "sma_crossover,bollinger",
}


@pytest.fixture(scope="module")
def certify_bench():
    """One tiny in-process certify run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _CERTIFY_ENV}
    os.environ.update(_CERTIFY_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_certify_wall_keys_present(certify_bench):
    """The certifier's analysis cost rides BENCH JSON like every other
    stage: certify_wall_s per family + the digest cones, and a rows
    count matching families x substrates x forms + 3 digest cones
    (scenario_synth, scenario_fused, splice)."""
    cf = certify_bench["roofline"]["certify"]
    for key in ("certify_wall_s", "rows", "wall_s_total"):
        assert key in cf, key
    walls = cf["certify_wall_s"]
    assert set(walls) == {"sma_crossover", "bollinger", "digest"}
    assert all(w > 0.0 for w in walls.values())
    assert cf["rows"] == 2 * 4 + 3
    assert cf["wall_s_total"] > 0.0
    assert certify_bench["configs"]["certify"] > 0.0


_MC_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "modelcheck",
    # Tiny-but-real: a short dbxmc sweep through the REAL explorer on
    # every available substrate — the analysis cost instrument, not the
    # invariant gate (that lives in test_mc_clean.py).
    "DBX_BENCH_MC_SCHEDULES": "30",
}


@pytest.fixture(scope="module")
def modelcheck_bench():
    """One tiny in-process dbxmc run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _MC_ENV}
    os.environ.update(_MC_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_modelcheck_bench_keys(modelcheck_bench):
    """dbxmc's exploration cost rides BENCH JSON like every other CI
    stage: schedules/crash_points/wall_s summed over the available
    substrates, plus a violations count that must read zero on a
    healthy tree."""
    mc = modelcheck_bench["roofline"]["modelcheck"]
    for key in ("schedules", "crash_points", "boundaries", "violations",
                "wall_s"):
        assert key in mc, key
    n_subs = 1 + (1 if native_core.available() else 0)
    assert mc["schedules"] == 30 * n_subs
    assert mc["crash_points"] >= 10 * n_subs
    assert mc["boundaries"] > mc["crash_points"]
    assert mc["violations"] == 0
    assert mc["wall_s"] > 0.0
    assert modelcheck_bench["configs"]["modelcheck"] > 0.0


_FANOUT_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "fanout",
    # Tiny-but-real: 12 subscriptions over 3 symbol chains on 4
    # connections — the serving-cost invariant (advances == unique
    # streams, pushes_per_advance == subs/streams) is exact at any
    # size; the p99 bar gets its real numbers from the full-size run.
    "DBX_BENCH_SUB_N": "12", "DBX_BENCH_SUB_SYMBOLS": "3",
    "DBX_BENCH_SUB_CONNS": "4",
}


@pytest.fixture(scope="module")
def fanout_bench():
    """One tiny in-process fanout run (loopback gRPC, streaming
    Subscribe calls, instant backend), shared by the module."""
    prior = {k: os.environ.get(k) for k in _FANOUT_ENV}
    os.environ.update(_FANOUT_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_fanout_keys_present(fanout_bench):
    """The live fan-out acceptance numbers ride these BENCH JSON keys
    (advances_per_tick == unique streams, pushes_per_advance, the p99
    latency bar) — a renamed key would silently invalidate BENCH_r12's
    successors. The tiny run's invariants are exact: 3 ticks, 3
    advances, 12 pushes, nothing dropped."""
    fb = fanout_bench["roofline"]["fanout"]
    for key in ("subscriptions", "symbols", "unique_streams", "ticks",
                "advances_total", "advances_per_tick",
                "advances_eq_streams", "pushes_delivered",
                "pushes_dropped", "pushes_per_advance",
                "tick_to_push_p50_s", "tick_to_push_p99_s", "p99_bar_s",
                "p99_ok", "tick_wall_s", "drain_wall_s"):
        assert key in fb, key
    assert fb["advances_total"] == 3
    assert fb["advances_per_tick"] == 1.0
    assert fb["advances_eq_streams"] is True
    assert fb["pushes_delivered"] == 12
    assert fb["pushes_dropped"] == 0
    assert fb["pushes_per_advance"] == 4.0
    assert fb["tick_to_push_p99_s"] > 0.0


_TENANT_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "e2e_local_tenants,scenario_sweep",
    # Tiny-but-real loopback drains + generator runs — structure smoke,
    # not performance; the 2x fairness bar is asserted on the real-size
    # run, not here (tiny samples make p95 noisy).
    "DBX_BENCH_TENANT_SMALL_JOBS": "6", "DBX_BENCH_TENANT_WHALE_JOBS": "18",
    "DBX_BENCH_TENANT_WHALE_COMBOS": "16",
    "DBX_BENCH_SCENARIO_BARS": "192", "DBX_BENCH_SCENARIO_N": "4",
}


@pytest.fixture(scope="module")
def tenant_bench():
    """One tiny in-process e2e_local_tenants + scenario_sweep run (loopback
    gRPC, instant backend, tiny generator shapes), shared by the module."""
    prior = {k: os.environ.get(k) for k in _TENANT_ENV}
    os.environ.update(_TENANT_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_tenant_fairness_keys_present(tenant_bench):
    """The 3-tenant adversarial A/B's acceptance numbers ride these BENCH
    JSON keys (tenant_p95_queue_wait_{solo,contended} + the ratio) — a
    renamed key would silently invalidate the next round's measurement."""
    tb = tenant_bench["roofline"]["e2e_local_tenants"]
    for key in ("small_jobs", "whale_jobs", "small_combos_per_job",
                "whale_combos_per_job", "tenant_p95_queue_wait_solo",
                "tenant_p95_queue_wait_contended", "fairness_ratio",
                "fairness_ok", "per_tenant_p95_contended",
                "jobs_per_s_solo", "jobs_per_s_contended"):
        assert key in tb, key
    assert tb["tenant_p95_queue_wait_solo"] > 0.0
    assert tb["tenant_p95_queue_wait_contended"] > 0.0
    assert tb["jobs_per_s_contended"] > 0.0
    for t in ("whale", "small_a", "small_b"):
        assert t in tb["per_tenant_p95_contended"], t
    assert tenant_bench["configs"]["e2e_local_tenants"] > 0.0


def test_scenario_sweep_keys_present(tenant_bench):
    """Scenario synthesis facts: generator rate, the (digest, params)
    spec-vs-panel wire columns, e2e dispatcher-materialized drain, and
    — structurally true at ANY scale — bit-reproducible digests."""
    sc = tenant_bench["roofline"]["scenario_sweep"]
    for key in ("panels", "bars", "gen_s_per_panel", "panels_per_s",
                "bar_rate", "digest_deterministic", "panel_bytes",
                "spec_bytes", "spec_wire_reduction", "jobs_per_s_e2e"):
        assert key in sc, key
    assert sc["digest_deterministic"] is True
    assert sc["panels_per_s"] > 0.0
    assert sc["jobs_per_s_e2e"] > 0.0
    assert sc["spec_bytes"] < sc["panel_bytes"]
    assert tenant_bench["configs"]["scenario_sweep"] > 0.0


_MEGAKERNEL_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "scenario_megakernel",
    # Tiny-but-real fused-vs-materialized A/B drains (loopback gRPC,
    # real JAX worker) — structure smoke; the 10x throughput bar is
    # asserted on the real-size run, not here. The store-bytes-flat-in-K
    # invariant IS structural and holds at any scale.
    "DBX_BENCH_MEGAKERNEL_BARS": "96", "DBX_BENCH_MEGAKERNEL_K": "4",
}


@pytest.fixture(scope="module")
def megakernel_bench():
    """One tiny in-process scenario_megakernel A/B run, shared by the
    module."""
    prior = {k: os.environ.get(k) for k in _MEGAKERNEL_ENV}
    os.environ.update(_MEGAKERNEL_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_scenario_megakernel_keys_present(megakernel_bench):
    """The round-18 acceptance numbers ride these BENCH JSON keys (the
    fused-vs-materialized scenarios/s ratio and the store-bytes-vs-K
    curve) — a renamed key would silently invalidate the next round's
    measurement."""
    mk = megakernel_bench["roofline"]["scenario_megakernel"]
    for key in ("scenarios", "bars", "combos", "fused_scn_per_s",
                "materialized_scn_per_s", "speedup",
                "store_bytes_by_k_fused", "store_bytes_by_k_materialized",
                "store_bytes_flat_in_k"):
        assert key in mk, key
    assert mk["fused_scn_per_s"] > 0.0
    assert mk["materialized_scn_per_s"] > 0.0
    assert megakernel_bench["configs"]["scenario_megakernel"] > 0.0


def test_scenario_megakernel_store_bytes_flat_in_k(megakernel_bench):
    """Device/store residency is O(1) in K on the fused route: every
    curve point holds exactly the base panel (1 entry, same byte count),
    while the materialized route's store grows with K — the structural
    half of the megakernel claim, true at any scale."""
    mk = megakernel_bench["roofline"]["scenario_megakernel"]
    fused = mk["store_bytes_by_k_fused"]
    mat = mk["store_bytes_by_k_materialized"]
    assert len(fused) >= 2 and len(mat) >= 2
    assert mk["store_bytes_flat_in_k"] is True
    assert len({p["store_bytes"] for p in fused}) == 1
    assert all(p["store_panels"] == 1 for p in fused)
    # Materialized stores base + K scenario panels: strictly growing.
    ks = [p["k"] for p in mat]
    assert all(p["store_panels"] == p["k"] + 1 for p in mat)
    bytes_by_k = [p["store_bytes"] for p in mat]
    assert bytes_by_k == sorted(bytes_by_k) and ks == sorted(ks)
    assert bytes_by_k[-1] > fused[-1]["store_bytes"]


_RAGGED_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "ragged_paged",
    # Tiny-but-real mixed-length fleet through the page pool (CPU
    # interpret mode) — structure smoke; the 1.3x ratio bar is asserted
    # on the real-size run, not here.
    "DBX_BENCH_RAGGED_TICKERS": "6", "DBX_BENCH_RAGGED_SPREAD": "3",
    "DBX_BENCH_BARS": "96", "DBX_BENCH_ITERS": "1",
    "DBX_BENCH_WARMUP": "0", "DBX_PAGE_BARS": "16",
}


@pytest.fixture(scope="module")
def ragged_bench():
    """One tiny in-process ragged_paged run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _RAGGED_ENV}
    os.environ.update(_RAGGED_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_ragged_paged_keys_present(ragged_bench):
    """The ragged paged A/B's acceptance numbers (paged_vs_uniform_ratio
    <= 1.3 at real scale, the launch/pad-bar savings and the pool
    residency cost) ride these BENCH JSON keys — a renamed key would
    silently invalidate the next round's measurement."""
    rp = ragged_bench["roofline"]["ragged_paged"]
    for key in ("tickers", "t_max", "t_min", "total_bars", "uniform_bars",
                "combos", "page_bars", "paged_s_per_sweep",
                "uniform_s_per_sweep", "paged_vs_uniform_ratio",
                "ratio_ok", "launches_dense", "launches_paged",
                "pad_bars_dense", "pad_bars_paged", "pool_bytes",
                "pool_bytes_per_ticker"):
        assert key in rp, key
    assert rp["paged_s_per_sweep"] > 0.0
    assert rp["uniform_s_per_sweep"] > 0.0
    assert rp["paged_vs_uniform_ratio"] > 0.0
    assert rp["launches_paged"] >= 1
    # The pad saving is structural (one page per ticker vs up-to-2x
    # bucket padding), true at any scale with a mixed-length fleet.
    assert rp["pad_bars_paged"] <= rp["tickers"] * rp["page_bars"]
    assert rp["pool_bytes"] > 0
    assert ragged_bench["configs"]["ragged_paged"] > 0.0


def test_streaming_append_keys_present(stream_bench):
    """The streaming A/B's acceptance numbers (append_speedup at the
    headline T=8192/ΔT=16, and the delta-vs-full wire columns) ride
    these BENCH JSON keys — a renamed key would silently invalidate the
    next round's measurement."""
    sa = stream_bench["roofline"]["streaming_append"]
    for key in ("bars_base", "delta_bars", "updates", "combos",
                "append_s_per_update", "full_reprice_s_per_update",
                "append_speedup", "wire_bytes_full", "wire_bytes_delta",
                "wire_reduction"):
        assert key in sa, key
    assert sa["append_s_per_update"] > 0.0
    assert sa["full_reprice_s_per_update"] > 0.0
    assert sa["append_speedup"] > 0.0
    # The wire saving is structural (ΔT vs T+ΔT bars), true at any scale.
    assert sa["wire_bytes_delta"] < sa["wire_bytes_full"]
    assert stream_bench["configs"]["streaming_append"] > 0.0


_AUTOTUNE_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "autotune",
    # Tiny-but-real: a handful of measured candidates per family on tiny
    # shapes, and a small compile probe through the REAL gRPC compile
    # exchange — structure smoke; the 1.2x / 5x acceptance bars are
    # asserted on the real-size run (BENCH_r10.json), not here.
    "DBX_BENCH_AUTOTUNE_BARS": "64", "DBX_BENCH_AUTOTUNE_TICKERS": "2",
    "DBX_BENCH_AUTOTUNE_COMPILE_DEPTH": "4",
    "DBX_AUTOTUNE_TRIALS": "2", "DBX_BENCH_ITERS": "1",
}


@pytest.fixture(scope="module")
def autotune_bench():
    """One tiny in-process autotune run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _AUTOTUNE_ENV}
    prior["DBX_AUTOTUNE"] = os.environ.pop("DBX_AUTOTUNE", None)
    os.environ.update(_AUTOTUNE_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


_PIPELINE_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "pipeline",
    # Tiny-but-real: a short saturated drain through the REAL gRPC
    # worker loop in both loop modes — structure smoke; the 1.4x / 1.6x
    # acceptance bars are asserted on the real-size run (BENCH_r13.json),
    # not here.
    "DBX_BENCH_PIPELINE_JOBS": "8", "DBX_BENCH_PIPELINE_BARS": "128",
    "DBX_BENCH_PIPELINE_FAST": "2", "DBX_BENCH_PIPELINE_SLOW": "2",
    "DBX_BENCH_PIPELINE_BATCH": "2",
    "DBX_BENCH_PIPELINE_DEVICE_MS": "3",
}


@pytest.fixture(scope="module")
def pipeline_bench():
    """One tiny in-process pipeline A/B run, shared by the module."""
    prior = {k: os.environ.get(k) for k in _PIPELINE_ENV}
    for knob in ("DBX_PIPELINE", "DBX_PREFETCH"):
        prior[knob] = os.environ.pop(knob, None)
    os.environ.update(_PIPELINE_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_pipeline_ab_keys_present(pipeline_bench):
    """The round-14 pipelined-executor A/B's acceptance numbers
    (pipeline_speedup, overlap_factor, the per-stage before/after
    attribution) ride these BENCH JSON keys — a renamed key would
    silently invalidate BENCH_r13's acceptance record."""
    pl = pipeline_bench["roofline"]["pipeline"]
    for key in ("jobs", "bars", "combos_per_job", "batch",
                "host_stage_ms", "device_stage_ms",
                "jobs_per_s_serial", "jobs_per_s_pipelined",
                "pipeline_speedup", "overlap_factor",
                "overlap_factor_serial", "stages_serial",
                "stages_pipelined"):
        assert key in pl, key
    assert pl["jobs_per_s_serial"] > 0.0
    assert pl["jobs_per_s_pipelined"] > 0.0
    assert pl["pipeline_speedup"] > 0.0
    # Overlap factors are ratios >= ~1; no performance bar here (tiny
    # shapes on a loaded CI core), but the serial arm must never read
    # as pipelined.
    assert pl["overlap_factor"] >= 1.0
    assert pl["overlap_factor_serial"] == pytest.approx(1.0, abs=0.25)
    # The before/after stage attribution actually attributed: both arms
    # saw decode (host staging) and d2h (device drain) walls.
    for stages in (pl["stages_serial"], pl["stages_pipelined"]):
        assert stages.get("decode", 0.0) > 0.0
        assert stages.get("d2h", 0.0) > 0.0
    assert pipeline_bench["configs"]["pipeline"] > 0.0


_FLEET_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "fleet_telemetry",
    # Tiny-but-real: a short direct-dispatch A/B plus a 2-worker
    # loopback drain with real telemetry frames — structure smoke; the
    # <=5% overhead and staleness bars are asserted on the real-size
    # run (BENCH_r14.json), not here (tiny samples are noise).
    "DBX_BENCH_LOCAL_JOBS": "96", "DBX_BENCH_FLEET_JOBS": "48",
    "DBX_BENCH_FLEET_WORKERS": "2", "DBX_BENCH_FLEET_POLL_S": "0.1",
}


@pytest.fixture(scope="module")
def fleet_bench():
    """One tiny in-process fleet_telemetry run (loopback gRPC, instant
    backend, real telemetry frames + FleetView), shared by the module."""
    prior = {k: os.environ.get(k) for k in _FLEET_ENV}
    os.environ.update(_FLEET_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_fleet_telemetry_keys_present(fleet_bench):
    """The fleet telemetry plane's acceptance numbers
    (telemetry_overhead_pct <= 5 with the 2k floor holding,
    fleet_staleness_p95_s <= 2 poll periods, frame_bytes_p50) ride
    these BENCH JSON keys — a renamed key would silently invalidate
    BENCH_r14's successors. Structurally true at any scale: both A/B
    arms drain, frames flow, and every live worker is visible in the
    merged view."""
    ft = fleet_bench["roofline"]["fleet_telemetry"]
    for key in ("jobs", "batch", "jobs_per_s_off", "jobs_per_s_on",
                "telemetry_overhead_pct", "overhead_ok", "floor_ok",
                "frame_bytes_p50", "frames_sampled", "e2e_jobs",
                "e2e_workers", "e2e_poll_s", "workers_seen",
                "all_workers_visible", "fleet_staleness_p95_s",
                "staleness_bar_s", "staleness_ok", "straggler_flagged",
                "histogram_merge_exact"):
        assert key in ft, key
    assert ft["jobs_per_s_off"] > 0.0
    assert ft["jobs_per_s_on"] > 0.0
    # Frames really flowed, and the merged /fleet.json saw every worker
    # (the 2 instant workers + the fast/slow straggler probes).
    assert ft["frame_bytes_p50"] > 0
    assert ft["frames_sampled"] >= 1
    assert ft["workers_seen"] == ft["e2e_workers"] + 2
    assert ft["all_workers_visible"] is True
    assert ft["fleet_staleness_p95_s"] >= 0.0
    # Structurally true at any scale: the slowed probe's execute EWMA
    # sits far above the healthy bulk's p95, and the fleet histogram is
    # the exact fold of the per-worker rows.
    assert ft["straggler_flagged"] is True
    assert ft["histogram_merge_exact"] is True
    assert fleet_bench["configs"]["fleet_telemetry"] > 0.0


_FLIGHT_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "flight",
    # Tiny-but-real: a short recorder-armed direct-dispatch A/B plus the
    # deterministic synthetic residual feed — structure smoke; the <=2%
    # overhead bar is asserted on the real-size run (tiny samples are
    # noise), but the residual math is exact at any scale.
    "DBX_BENCH_LOCAL_JOBS": "96", "DBX_COSTMODEL": "1",
}


@pytest.fixture(scope="module")
def flight_bench():
    """One tiny in-process flight run (loopback gRPC, armed recorder in a
    tempdir, synthetic residual stream), shared by the module."""
    prior = {k: os.environ.get(k) for k in _FLIGHT_ENV}
    for knob in ("DBX_FLIGHT_DIR", "DBX_COSTMODEL_WARMUP",
                 "DBX_COSTMODEL_BLOWOUT"):
        prior[knob] = os.environ.pop(knob, None)
    os.environ.update(_FLIGHT_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_flight_keys_present(flight_bench):
    """The flight recorder's acceptance numbers (recorder-armed overhead
    <= 2% on the direct_dispatch floor, zero bundles on the happy path)
    and the drift plane's costmodel_residual_{p50,p95} ride these BENCH
    JSON keys — a renamed key would silently invalidate the round-17
    acceptance record. Structurally true at any scale: the armed cycle
    writes NO bundles (the hot path never captures), capture_now really
    writes one, and the synthetic residual stream is exact math — 20
    scored observations, exactly one past the blowout bar."""
    fl = flight_bench["roofline"]["flight"]
    for key in ("jobs", "batch", "jobs_per_s_off", "jobs_per_s_on",
                "overhead_pct", "overhead_ok", "floor_ok",
                "bundles_during_run", "quiet_ok", "capture_smoke_ok",
                "costmodel_obs", "costmodel_blowouts",
                "costmodel_residual_p50", "costmodel_residual_p95"):
        assert key in fl, key
    assert fl["jobs_per_s_off"] > 0.0
    assert fl["jobs_per_s_on"] > 0.0
    assert fl["bundles_during_run"] == 0
    assert fl["quiet_ok"] is True
    assert fl["capture_smoke_ok"] is True
    # The synthetic feed is deterministic: warmup_n()-1 calibration obs
    # after the seed, then 20 drifted durations computed FROM the op
    # model — 20 scored residuals, the first (+3.5 log2) past the
    # default 3.0 blowout bar, tail above body.
    assert fl["costmodel_obs"] == 20
    assert fl["costmodel_blowouts"] == 1
    assert fl["costmodel_residual_p95"] >= fl["costmodel_residual_p50"]
    assert flight_bench["configs"]["flight"] > 0.0


def test_autotune_keys_present(autotune_bench):
    """The substrate-autotuner A/B's acceptance numbers
    (autotuned_vs_default_speedup{family} with its modeled twin) ride
    these BENCH JSON keys — a renamed
    key would silently invalidate the next round's measurement."""
    at = autotune_bench["roofline"]["autotune"]
    for key in ("autotuned_vs_default_speedup",
                "autotuned_vs_default_speedup_modeled", "families",
                "speedup_families_ok", "platform"):
        assert key in at, key
    # >= 3 kernel families measured, each with a winner recorded.
    assert len(at["autotuned_vs_default_speedup"]) >= 3
    for fam, row in at["families"].items():
        assert row["default_s_per_sweep"] > 0.0, fam
        assert row["tuned_s_per_sweep"] > 0.0, fam
        assert at["autotuned_vs_default_speedup"][fam] > 0.0, fam
        assert at["autotuned_vs_default_speedup_modeled"][fam] > 0.0, fam
    assert autotune_bench["configs"]["autotune"] > 0.0


_DECISION_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "decision_plane",
    # Tiny-but-real: five short paired killed/armed direct-dispatch
    # rounds plus the deterministic synthetic shadow stream. The <=2%
    # median-paired-delta bar is asserted on the real-size run (tiny
    # samples are pure noise), but the shadow math is exact at any
    # scale.
    "DBX_BENCH_LOCAL_JOBS": "96",
}


@pytest.fixture(scope="module")
def decision_bench():
    """One tiny in-process decision_plane run (loopback gRPC A/B plus
    the synthetic two-worker shadow stream), shared by the module."""
    prior = {k: os.environ.get(k) for k in _DECISION_ENV}
    for knob in ("DBX_DECISIONS", "DBX_DECISIONS_RATE",
                 "DBX_DECISIONS_H2D_GBPS"):
        prior[knob] = os.environ.pop(knob, None)
    os.environ.update(_DECISION_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_decision_plane_keys_present(decision_bench):
    """The decision plane's acceptance numbers (recorder-armed median
    paired delta on the direct_dispatch floor, shadow-scorer agreement
    and regret percentiles) ride these BENCH JSON keys — a renamed key
    would silently invalidate the round-19 acceptance record. The
    overhead/floor verdicts are asserted as present, not True: at 96
    jobs the paired deltas are box noise, and the bar belongs to the
    real-size run. The shadow stream IS exact at any scale: 16
    deterministic decisions over a two-worker fleet, 12 placed on the
    panel-resident worker — agreement is 75% by construction and every
    mis-placement's regret is the panel's h2d wall."""
    dp = decision_bench["roofline"]["decision_plane"]
    for key in ("jobs", "batch", "jobs_per_s_off", "jobs_per_s_on",
                "decision_overhead_delta_pct", "overhead_rounds_pct",
                "overhead_ok", "floor_ok", "shadow_scored",
                "shadow_agreement_pct", "regret_p50", "regret_p95",
                "regret_expected_s"):
        assert key in dp, key
    assert dp["jobs_per_s_off"] > 0.0
    assert dp["jobs_per_s_on"] > 0.0
    assert len(dp["overhead_rounds_pct"]) == 5
    assert isinstance(dp["overhead_ok"], bool)
    assert isinstance(dp["floor_ok"], bool)
    # Deterministic synthetic stream: all 16 scored, 12/16 agree.
    assert dp["shadow_scored"] == 16
    assert dp["shadow_agreement_pct"] == 75.0
    assert dp["regret_expected_s"] > 0.0
    assert dp["regret_p95"] >= dp["regret_p50"] >= 0.0
    assert decision_bench["configs"]["decision_plane"] > 0.0


_PLACEMENT_ENV = {
    "DBX_BENCH_CPU": "1", "DBX_BENCH_CACHE": "",
    "DBX_BENCH_CONFIGS": "e2e_local_placement",
    # Tiny-but-real: 3 chains x 4 links plus the repeat/cold tail,
    # virtual stage costs scaled down 4x so the A/B finishes in seconds.
    "DBX_BENCH_PL_SCALE": "0.25",
    "DBX_BENCH_PL_CHAINS": "3",
    "DBX_BENCH_PL_LINKS": "4",
}


@pytest.fixture(scope="module")
def placement_bench():
    """One tiny in-process e2e_local_placement A/B (locality-blind vs
    live placement over two loopback workers), shared by the module."""
    prior = {k: os.environ.get(k) for k in _PLACEMENT_ENV}
    for knob in ("DBX_PLACEMENT", "DBX_PLACEMENT_DEFER_CAP",
                 "DBX_DECISIONS", "DBX_DECISIONS_RATE",
                 "DBX_DECISIONS_H2D_GBPS"):
        prior[knob] = os.environ.pop(knob, None)
    os.environ.update(_PLACEMENT_ENV)
    bench.ROOFLINE.clear()
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench.main()
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_placement_keys_present(placement_bench):
    """Round-20 acceptance numbers (placement_speedup >= 1.5x vs the
    locality-blind arm, live regret strictly below the round-19 shadow
    baseline) ride these BENCH JSON keys — a renamed key would silently
    invalidate the acceptance record. Keys-present only: at 3x4 links
    with scaled virtual costs the speedup and regret verdicts are box
    noise, and the bar belongs to the real-size run. The structural
    facts ARE exact at any scale: both arms score every take, and the
    admit counters partition every placement-gate consultation."""
    pl = placement_bench["roofline"]["e2e_local_placement"]
    for key in ("jobs", "workers", "jobs_per_s_blind", "jobs_per_s_live",
                "placement_speedup", "defer_rate", "admit_counts",
                "regret_seconds_shadow", "regret_seconds_live",
                "scored_shadow", "scored_live", "speedup_ok",
                "regret_ok"):
        assert key in pl, key
    assert pl["jobs"] > 0 and pl["workers"] == 2
    assert pl["jobs_per_s_blind"] > 0.0
    assert pl["jobs_per_s_live"] > 0.0
    assert pl["scored_shadow"] > 0 and pl["scored_live"] > 0
    assert 0.0 <= pl["defer_rate"] <= 1.0
    assert set(pl["admit_counts"]) <= {"served", "deferred", "cap"}
    assert pl["admit_counts"]["served"] > 0
    assert isinstance(pl["speedup_ok"], bool)
    assert isinstance(pl["regret_ok"], bool)
    assert placement_bench["configs"]["e2e_local_placement"] > 0.0
