#!/usr/bin/env python3
"""Chip smoke: drive the served path once on the chip. A smoke run, not a
benchmark.

The path is the one users run: an in-process ``DispatcherServer`` on
``127.0.0.1:0`` -> gRPC -> ``Worker`` -> ``JaxSweepBackend`` (default
settings: fused, paged and pipelined all on by auto) -> the fused Pallas
kernels. One process holds the chip; nothing here starts a child that
touches JAX.

``python chip_smoke.py`` (one chip) enqueues the north-star universe sweep
(500 tickers x 1,260 bars, ``sma_crossover``, ``fast=5:25,slow=30:230:2``
= 2,000 combos), a few jobs for every other fused family and pairs,
ragged-length jobs (of different lengths in one batch) for SMA and the
band families, and a scenario batch, drains them, and fails unless every job
completed through the fused path with no failed or requeued job and no
``*_fail`` flight trigger, and sampled rows agree with the generic ``jnp``
reference (``models/`` + ``parallel.sweep.jit_sweep``) within the budgets
``bench.py --verify`` asserts (``utils/verify.py``).

``python chip_smoke.py --chips 4`` runs only the four-chip path: the mesh
worker over the SMA universe plus one job longer than the fused bar cap
(the time-sharded route), both against the one-device generic reference,
and checks that every result's sharding spans all four chips.

The last line of standard output is ``{"ok": true, "device": {...}}``;
any failure exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import threading
import time

import numpy as np

from distributed_backtesting_exploration_tpu import obs
from distributed_backtesting_exploration_tpu.rpc import compute, wire
from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
    Dispatcher, DispatcherServer, JobQueue, JobRecord, PeerRegistry,
    parse_grid, scenario_jobs, synthetic_jobs)
from distributed_backtesting_exploration_tpu.rpc.panel_store import (
    panel_digest)
from distributed_backtesting_exploration_tpu.rpc.worker import Worker
from distributed_backtesting_exploration_tpu.tune import compile_cache
from distributed_backtesting_exploration_tpu.utils import data as data_mod
from distributed_backtesting_exploration_tpu.utils import verify

COST = 1e-3
NORTH_STAR_GRID = "fast=5:25,slow=30:230:2"
# One grid per other fused family, cut from bench.py --verify's cases.
FAMILY_GRIDS = {
    "bollinger": "window=10:50:2,k=0.5;1.0;1.5;2.0;2.5;3.0",
    "bollinger_touch": "window=10:50:2,k=0.5;1.0;1.5;2.0;2.5;3.0",
    "momentum": "lookback=5:85:2",
    "donchian": "window=10:90:2",
    "donchian_hl": "window=10:90:2",
    "rsi": "period=5:45:2,band=10;15;20;25;30",
    "stochastic": "window=5:85:2,band=10;20;30;40",
    "keltner": "window=5:85:2,k=1.0;1.5;2.0;2.5;3.0",
    "macd": "fast=5:13,slow=20:52:8,signal=5;9",
    "trix": "span=5:45:2,signal=4;9",
    "vwap_reversion": "window=10:50:2,k=0.5;1.0;1.5;2.0;2.5;3.0",
    "obv_trend": "window=5:85:2",
    "pairs": "lookback=10:50:2,z_entry=0.5;1.0;1.5;2.0;2.5;3.0",
}
# Families sent jobs of mixed lengths in one batch: SMA runs them as one
# ragged group; the band families must come back as if each length ran
# alone (rpc.compute._EXACT_LENGTH_STRATEGIES, ROADMAP B7).
RAGGED_FAMILIES = ("sma_crossover", "bollinger", "bollinger_touch",
                   "vwap_reversion", "pairs")
SCENARIO_PARAMS = {"block": 16, "regimes": 2, "vol_scale": 1.5,
                   "shock": 0.01}
# The four-chip long job: past rpc.compute's fused bar cap, on a grid the
# time-sharded route compiles per combo (<= _TIMESHARD_MAX_COMBOS).
LONG_GRID = "fast=5:25:5,slow=50:250:50"
SCENARIO_BASE = "scenario-base"
FAIL_TRIGGERS = ("collect_fail", "scenario_fused_fail", "job_fail",
                 "other")


class SmokeFailure(RuntimeError):
    """A phase of the smoke failed."""


def _say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# Workload
# ---------------------------------------------------------------------------

def build_jobs(*, tickers: int, bars: int, family_tickers: int,
               family_grids: dict, scenarios: int,
               ragged_bars: tuple = (), ragged_tickers: int = 0,
               north_star_grid: str = NORTH_STAR_GRID,
               long_bars: int = 0, long_grid: str = LONG_GRID,
               seed: int = 0) -> list[JobRecord]:
    """The smoke's job records, data made from ``seed``: the north-star
    universe, ``family_tickers`` uniform-length jobs per other family (as
    ``bench.py --verify`` measures them), and ``ragged_tickers`` jobs per
    :data:`RAGGED_FAMILIES` member cycling through ``ragged_bars`` —
    lengths in one page-count class, so SMA runs them as one ragged group
    (per-ticker lengths in SMEM)."""
    grids = {"sma_crossover": north_star_grid, **family_grids}
    recs = synthetic_jobs(tickers, bars, "sma_crossover",
                          parse_grid(north_star_grid), cost=COST, seed=seed)
    for i, (strategy, spec) in enumerate(sorted(family_grids.items())):
        recs += synthetic_jobs(family_tickers, bars, strategy,
                               parse_grid(spec), cost=COST,
                               seed=seed + 1000 + i)
    for i, strategy in enumerate(s for s in RAGGED_FAMILIES if s in grids):
        for j in range(ragged_tickers):
            recs += synthetic_jobs(1, ragged_bars[j % len(ragged_bars)],
                                   strategy, parse_grid(grids[strategy]),
                                   cost=COST, seed=seed + 2000 + 100 * i + j)
    if long_bars:
        recs += synthetic_jobs(1, long_bars, "sma_crossover",
                               parse_grid(long_grid), cost=COST,
                               seed=seed + 7)
    if scenarios:
        # One base panel and `scenarios` digest-seeded specs over it: the
        # worker regenerates each scenario panel inside the sweep launch.
        base = synthetic_jobs(1, bars, "sma_crossover",
                              parse_grid(north_star_grid), cost=COST,
                              seed=seed + 9)[0]
        base.id = SCENARIO_BASE
        recs.append(base)
        recs += scenario_jobs(
            panel_digest(base.ohlcv), scenarios, "sma_crossover",
            parse_grid(north_star_grid),
            params={**SCENARIO_PARAMS, "n_bars": bars}, cost=COST)
    return recs


# ---------------------------------------------------------------------------
# Drain through the served path
# ---------------------------------------------------------------------------

# jax's own compile-path timers, summed over the process: they split the
# first-shape submit wall into trace, lowering and backend compile (a
# persistent-cache hit is counted, and its load timed, as a compile).
_JAX_TIMERS = {"/jax/core/compile/jaxpr_trace_duration": "jax:trace_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration":
                   "jax:lower_s",
               "/jax/core/compile/backend_compile_duration":
                   "jax:compile_s"}
_JAX_EVENTS = {"/jax/compilation_cache/cache_hits": "jax:cache_hits",
               "/jax/compilation_cache/cache_misses": "jax:cache_misses"}
_jax_sums: dict = {}


def _watch_jax() -> None:
    if _jax_sums:
        return
    import jax

    _jax_sums.update({k: 0 for k in (*_JAX_TIMERS.values(),
                                     *_JAX_EVENTS.values())})

    def on_duration(event, duration, **kw):
        if event in _JAX_TIMERS:
            _jax_sums[_JAX_TIMERS[event]] += duration

    def on_event(event, **kw):
        if event in _JAX_EVENTS:
            _jax_sums[_JAX_EVENTS[event]] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _counters() -> dict:
    _watch_jax()
    return {**obs.get_registry().summaries(prefix="dbx_"), **_jax_sums}


def _deltas(after: dict, before: dict) -> dict:
    """Counter deltas; histograms give their count delta, and their sum
    delta under ``<key>:sum``."""
    out = {}
    for key, a in after.items():
        b = before.get(key, 0)
        if isinstance(a, dict):
            b = b if isinstance(b, dict) else {}
            out[key] = a.get("count", 0) - b.get("count", 0)
            out[key + ":sum"] = a.get("sum", 0.0) - b.get("sum", 0.0)
        else:
            out[key] = a - b
    return out


def drain(recs: list[JobRecord], backend, *, jobs_per_chip: int,
          timeout_s: float):
    """Enqueue ``recs`` on an in-process dispatcher and drain them with
    one ``Worker`` over ``backend``. Returns ``(queue, dispatcher,
    seconds, counter deltas)``; raises :class:`SmokeFailure` on a
    backend failure or at ``timeout_s``."""
    # Leases and the prune window outlast the drain: a requeue in the
    # smoke is then a fault, never a slow first compile.
    queue = JobQueue(lease_s=timeout_s)
    queue.enqueue_many(recs)
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=timeout_s))
    srv = DispatcherServer(disp, bind="127.0.0.1:0",
                           prune_interval_s=5.0).start()
    worker = Worker(f"127.0.0.1:{srv.port}", backend, worker_id="smoke",
                    poll_interval_s=0.01, status_interval_s=0.5,
                    jobs_per_chip=jobs_per_chip)
    before = _counters()
    t0 = time.perf_counter()
    thread = threading.Thread(target=worker.run, name="smoke-worker",
                              daemon=True)
    thread.start()
    try:
        while not queue.drained:
            if worker.batches_failed:
                raise SmokeFailure(
                    f"{worker.batches_failed} batch(es) failed in the "
                    "backend (see the log above)")
            if time.perf_counter() - t0 > timeout_s:
                raise SmokeFailure(
                    f"drain did not finish in {timeout_s:.0f} s: "
                    f"{queue.stats()}")
            time.sleep(0.05)
        seconds = time.perf_counter() - t0
    finally:
        worker.stop()
        thread.join(timeout=60)
        srv.stop()
    if thread.is_alive():
        raise SmokeFailure("worker thread did not stop")
    if worker.batches_failed:
        raise SmokeFailure(f"{worker.batches_failed} batch(es) failed in "
                           "the backend")
    return queue, disp, seconds, _deltas(_counters(), before)


def check_drain(queue: JobQueue, disp: Dispatcher, deltas: dict,
                n_jobs: int) -> None:
    """Every job completed with a result; none failed or was requeued;
    no ``*_fail`` flight trigger fired."""
    st = queue.stats()
    if st["jobs_failed"] or st["jobs_requeued"]:
        raise SmokeFailure(f"failed/requeued jobs: {st}")
    if st["jobs_completed"] != n_jobs:
        raise SmokeFailure(f"{st['jobs_completed']} of {n_jobs} jobs "
                           f"completed: {st}")
    empty = [j for j, m in disp.results.items() if not m]
    if len(disp.results) != n_jobs or empty:
        raise SmokeFailure(f"{len(disp.results)} results for {n_jobs} "
                           f"jobs, {len(empty)} empty")
    fired = {t: deltas.get(f"dbx_flight_triggers_total{{trigger={t}}}", 0)
             for t in FAIL_TRIGGERS}
    if any(fired.values()):
        raise SmokeFailure(f"flight triggers fired: {fired}")


def check_routes(deltas: dict, strategies, *, scenarios: int = 0,
                 mesh: bool = False) -> dict:
    """The route counters show the fused path served every strategy (and
    the in-trace scenario route served every scenario)."""
    groups: dict = {}
    for key, n in deltas.items():
        if (key.startswith("dbx_kernel_submit_seconds{")
                and not key.endswith(":sum") and n):
            kernel = key.split("kernel=", 1)[1].split(",", 1)[0]
            route, strategy = kernel.split(":", 1)
            groups.setdefault(strategy, {}).setdefault(route, 0)
            groups[strategy][route] += int(n)
    fused_routes = (("fused_mesh", "timeshard") if mesh
                    else ("paged", "fused", "scenario"))
    bad = {}
    for s in strategies:
        routes = groups.get(s, {})
        if s == "pairs":
            served = sum(n for k, n in deltas.items()
                         if k.startswith("dbx_fused_substrate_total{")
                         and "kernel=pairs" in k)
            ok = served > 0
        else:
            ok = bool(routes) and set(routes) <= set(fused_routes)
        if not ok:
            bad[s] = routes
    if bad:
        raise SmokeFailure(f"strategies not served by the fused path: "
                           f"{bad}")
    if scenarios:
        fused = deltas.get("dbx_scenario_route_total{mode=fused}", 0)
        mat = deltas.get("dbx_scenario_route_total{mode=materialized}", 0)
        if fused != scenarios or mat:
            raise SmokeFailure(f"scenario route: fused {fused}, "
                               f"materialized {mat} (want {scenarios}, 0)")
    return groups


# ---------------------------------------------------------------------------
# Reference comparison
# ---------------------------------------------------------------------------

def _axes(rec: JobRecord) -> dict:
    # Through the wire, exactly as the worker reads it: the metric columns
    # are in this product_grid order.
    return wire.grid_from_proto(wire.grid_to_proto(rec.grid))


def _reference(recs: list[JobRecord]):
    """Generic ``jnp`` reference Sharpe rows of same-length, same-grid
    single-asset or pairs ``recs``."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.models import pairs
    from distributed_backtesting_exploration_tpu.parallel import sweep

    rec0 = recs[0]
    grid = sweep.product_grid(**_axes(rec0))

    def panel(blobs):
        series = [data_mod.from_wire_bytes(b) for b in blobs]
        return type(series[0])(*(jnp.asarray(np.stack(f))
                                 for f in zip(*series)))
    if rec0.strategy == "pairs":
        y = panel([r.ohlcv for r in recs]).close
        x = panel([r.ohlcv2 for r in recs]).close
        m = pairs.run_pairs_sweep(y, x, dict(grid), cost=rec0.cost)
    else:
        m = sweep.jit_sweep(panel([r.ohlcv for r in recs]),
                            base.get_strategy(rec0.strategy), dict(grid),
                            cost=rec0.cost)
    return np.asarray(m.sharpe)


def _served(disp: Dispatcher, recs: list[JobRecord]):
    return np.stack([np.asarray(wire.metrics_from_bytes(
        disp.results[r.id]).sharpe) for r in recs])


def compare(disp: Dispatcher, recs: list[JobRecord], sample: int) -> dict:
    """Served Sharpe rows vs the generic reference within
    ``utils.verify``'s budgets, one row per (strategy, grid): the first
    ``sample`` jobs of each length, each length's reference run apart."""
    groups: dict = {}
    for r in recs:
        if r.id == SCENARIO_BASE or r.scenario:
            continue
        grid = tuple((k, np.asarray(v).tobytes())
                     for k, v in sorted(r.grid.items()))
        groups.setdefault((r.strategy, grid), {}).setdefault(
            len(r.ohlcv), []).append(r)
    rows = {}
    for (strategy, _), by_length in groups.items():
        parts = [members[:sample] for members in by_length.values()]
        ref = np.concatenate([_reference(m) for m in parts])
        got = np.concatenate([_served(disp, m) for m in parts])
        name = (strategy if strategy not in rows
                else f"{strategy}@{ref.shape[1]}combos")
        rows[name] = verify.compare_sharpe(strategy, ref, got)
    over = sorted(s for s, r in rows.items() if r["over_budget"])
    if over:
        raise SmokeFailure(f"over the verify budget: "
                           f"{ {s: rows[s] for s in over} }")
    return rows


def compare_scenarios(disp: Dispatcher, recs: list[JobRecord]) -> dict:
    """Scenario rows vs the generic reference over the host-materialized
    scenario panel of each spec."""
    from distributed_backtesting_exploration_tpu import scenarios as scn
    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep

    base_rec = next(r for r in recs if r.id == SCENARIO_BASE)
    base_series = data_mod.from_wire_bytes(base_rec.ohlcv)
    digest = panel_digest(base_rec.ohlcv)
    specs = [r for r in recs if r.scenario]
    ref = []
    for r in specs:
        p = scn.ScenarioParams.from_dict(
            {k: v for k, v in r.scenario.items() if k != "base"})
        panel = scn.generate(base_series, p, scn.scenario_seed(digest, p))
        m = sweep.jit_sweep(
            type(panel)(*(np.asarray(f)[None, :] for f in panel)),
            base.get_strategy(r.strategy),
            dict(sweep.product_grid(**_axes(r))), cost=r.cost)
        ref.append(np.asarray(m.sharpe)[0])
    row = verify.compare_sharpe("scenario", np.stack(ref),
                                _served(disp, specs))
    if row["over_budget"]:
        raise SmokeFailure(f"scenario rows over the verify budget: {row}")
    return row


# ---------------------------------------------------------------------------
# The two runs
# ---------------------------------------------------------------------------

def run_smoke(*, tickers: int = 500, bars: int = 1260,
              family_tickers: int = 12, family_grids: dict | None = None,
              ragged_bars: tuple = (1100, 1040), ragged_tickers: int = 4,
              north_star_grid: str = NORTH_STAR_GRID, scenarios: int = 4,
              sample: int = 32, jobs_per_chip: int = 500,
              timeout_s: float = 900.0,
              backend_kwargs: dict | None = None) -> dict:
    """One-chip smoke: build, drain, check. Returns the report; raises
    :class:`SmokeFailure` on any failed phase."""
    family_grids = FAMILY_GRIDS if family_grids is None else family_grids
    recs = build_jobs(tickers=tickers, bars=bars,
                      family_tickers=family_tickers,
                      family_grids=family_grids, scenarios=scenarios,
                      ragged_bars=ragged_bars, ragged_tickers=ragged_tickers,
                      north_star_grid=north_star_grid)
    backend = compute.JaxSweepBackend(**(backend_kwargs or {}))
    if not backend.use_fused:
        raise SmokeFailure("the backend did not enable the fused path")
    queue, disp, seconds, deltas = drain(
        recs, backend, jobs_per_chip=jobs_per_chip, timeout_s=timeout_s)
    check_drain(queue, disp, deltas, len(recs))
    strategies = {"sma_crossover", *family_grids}
    routes = check_routes(deltas, strategies, scenarios=scenarios)
    t0 = time.perf_counter()
    rows = compare(disp, recs, sample)
    if scenarios:
        rows["scenario"] = compare_scenarios(disp, recs)
    return {
        "jobs": len(recs),
        "drain_s": seconds,
        "compile_s": _compile_seconds(deltas),
        "jax_compile": _jax_deltas(deltas),
        "reference_s": time.perf_counter() - t0,
        "routes": routes,
        "verify": rows,
    }


def run_mesh_smoke(*, tickers: int = 500, bars: int = 1260,
                   long_bars: int = 16384, north_star_grid: str =
                   NORTH_STAR_GRID, long_grid: str = LONG_GRID,
                   sample: int = 32, jobs_per_chip: int = 125,
                   timeout_s: float = 900.0, n_devices: int = 4,
                   backend_kwargs: dict | None = None) -> dict:
    """Four-chip smoke: the mesh worker over the SMA universe plus one
    job past the fused bar cap (time-sharded route), each result checked
    to span all ``n_devices`` chips and against the one-device generic
    reference."""
    recs = build_jobs(tickers=tickers, bars=bars, family_tickers=0,
                      family_grids={}, scenarios=0,
                      north_star_grid=north_star_grid,
                      long_bars=long_bars, long_grid=long_grid)
    if long_bars <= compute.JaxSweepBackend._FUSED_MAX_BARS:
        raise SmokeFailure(f"long job of {long_bars} bars is not past the "
                           "fused bar cap")
    backend = _ShardingProbe(**{"use_mesh": True, **(backend_kwargs or {})})
    if backend._mesh is None or backend._mesh.devices.size != n_devices:
        raise SmokeFailure(f"mesh worker over {n_devices} devices expected, "
                           f"got {backend._mesh}")
    queue, disp, seconds, deltas = drain(
        recs, backend, jobs_per_chip=jobs_per_chip, timeout_s=timeout_s)
    check_drain(queue, disp, deltas, len(recs))
    routes = check_routes(deltas, {"sma_crossover"}, mesh=True)
    if "timeshard" not in routes["sma_crossover"]:
        raise SmokeFailure(f"the long job did not take the time-sharded "
                           f"route: {routes}")
    narrow = [n for n in backend.result_device_counts if n != n_devices]
    if not backend.result_device_counts or narrow:
        raise SmokeFailure(f"result shardings span {narrow or 'no'} "
                           f"device(s), not all {n_devices}")
    t0 = time.perf_counter()
    rows = compare(disp, recs, sample)
    return {
        "jobs": len(recs),
        "drain_s": seconds,
        "compile_s": _compile_seconds(deltas),
        "jax_compile": _jax_deltas(deltas),
        "reference_s": time.perf_counter() - t0,
        "routes": routes,
        "result_device_counts": sorted(set(backend.result_device_counts)),
        "verify": rows,
    }


class _ShardingProbe(compute.JaxSweepBackend):
    """The mesh worker backend, recording how many devices each pending
    result spans (code never run on more than one chip may put every
    result on the first)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.result_device_counts: list[int] = []

    def submit(self, jobs):
        pending = super().submit(jobs)
        for entry in pending:
            for item in entry:
                sharding = getattr(item, "sharding", None)
                if sharding is not None:
                    self.result_device_counts.append(
                        len(sharding.device_set))
        return pending


def _compile_seconds(deltas: dict) -> float:
    """Submit wall of first-seen shapes (trace + compile + launch), from
    the backend's ``dbx_kernel_submit_seconds{phase=compile}``."""
    return sum(v for k, v in deltas.items()
               if k.startswith("dbx_kernel_submit_seconds{")
               and k.endswith("phase=compile}:sum"))


def _jax_deltas(deltas: dict) -> dict:
    """The drain's share of jax's compile-path timers and cache counts."""
    return {k.split(":", 1)[1]: deltas[k] for k in sorted(deltas)
            if k.startswith("jax:")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip served path (default); 4: only "
                         "the mesh and time-sharded paths")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    compile_cache.configure()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "this script runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    _say(f"device_kind {kind!r}, {len(devices)} device(s) "
         f"(smoke, not a benchmark)")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            report = run_mesh_smoke(n_devices=len(devices))
        else:
            report = run_smoke()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _say(f"{report['jobs']} jobs drained in {report['drain_s']:.3f} s; "
         f"first-shape submit wall {report['compile_s']:.3f} s; "
         f"reference {report['reference_s']:.3f} s; total "
         f"{time.perf_counter() - t0:.3f} s (smoke, not a benchmark)")
    _say(f"jax compile path during the drain "
         f"{json.dumps(report['jax_compile'], sort_keys=True)}")
    _say(f"routes {json.dumps(report['routes'], sort_keys=True)}")
    if "result_device_counts" in report:
        _say(f"result shardings span {report['result_device_counts']} "
             f"device(s)")
    for name, row in sorted(report["verify"].items()):
        _say(f"verify[{name}] {row['entry_flips']}/{row['cells']} flips, "
             f"{row['best_param_flips']}/{row['n_tickers']} best-param "
             f"flips, max rel err {row['max_rel_err_nonflip']}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
