"""One run of one cell: set-up, the measured window, the check, the
result line."""

from __future__ import annotations

import gc
import glob
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from harness import cell as cell_mod
from harness import check, serve, traffic
from harness import trace as trace_mod


# How long after the window closes the run waits for answers still due.
DRAIN_WAIT_S = 60.0
# A stop of the whole process longer than this is reported.
STALL_S = 0.5


class NoChip(RuntimeError):
    """JAX found no TPU, or not as many chips as the cell asks for."""


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


@dataclass
class Run:
    """What the metric readers read."""

    cell: dict
    config: dict
    seconds: float
    loop: str
    combos: int
    bars: int
    family: object
    device_kind: str
    setup_s: float = 0.0
    window_jobs: int = 0
    backtests: int = 0
    latencies: np.ndarray | None = None
    queue_waits: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    axes: dict = field(default_factory=dict)
    trace: trace_mod.Reduction | None = None
    window: tuple[float, float] | None = None

    def work(self) -> tuple[float, float]:
        """``(operations, bytes)`` of the jobs completed in the window."""
        return self.family.work(self.window_jobs, self.bars, self.axes)


class _Compiles:
    """Backend compiles seen by jax (a persistent-cache load counts)."""

    count = 0
    _on = False

    @classmethod
    def watch(cls) -> None:
        if cls._on:
            return
        import jax

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._on = True


class _GcPauses:
    """The process's garbage collections (every thread waits for them),
    each as ``(end, generation, seconds)``."""

    def __init__(self):
        self.pauses: list[tuple[float, int, float]] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((time.time(), int(info["generation"]),
                                time.perf_counter() - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def report(self, t0: float, t1: float) -> str:
        def part(ps):
            full = [p[2] for p in ps if p[1] == 2]
            return (f"{len(ps)} collections, {sum(p[2] for p in ps):.6f} s, "
                    f"{len(full)} full, longest {max(full, default=0.0):.6f} s")
        return (f"garbage collection before the window: "
                f"{part([p for p in self.pauses if p[0] < t0])}; in it: "
                f"{part([p for p in self.pauses if t0 <= p[0] <= t1])}")


class _StallWatch(threading.Thread):
    """Notes each stop of the process longer than ``STALL_S``: this
    thread wakes every 50 ms, and a longer gap means something held the
    interpreter (a collection, a call that keeps the lock) or the host
    did not schedule the process."""

    def __init__(self):
        super().__init__(name="benchmark-stall-watch", daemon=True)
        self.stalls: list[tuple[float, float]] = []   # (end, seconds)
        self.stop_event = threading.Event()

    def run(self) -> None:
        last = time.time()
        while not self.stop_event.is_set():
            time.sleep(0.05)
            now = time.time()
            if now - last > STALL_S:
                self.stalls.append((now, now - last))
            last = now

    def stop(self) -> None:
        self.stop_event.set()
        self.join(timeout=5)

    def report(self, t0: float, t1: float) -> str:
        inside = [d for end, d in self.stalls if t0 <= end <= t1 + STALL_S]
        return (f"stops of the process over {STALL_S} s in the window: "
                f"{len(inside)}, longest {max(inside, default=0.0):.6f} s")


def _cache_entries(cache_dir: str | None) -> set[str]:
    try:
        return set(os.listdir(cache_dir)) if cache_dir else set()
    except OSError:
        return set()


def _settle(svc, cache_dir: str | None, entries_before: set[str]) -> None:
    """Let set-up's after-effects pass before the window opens. A run that
    compiled has written cache entries that the worker offers to the
    dispatcher on its next compile-cache sync: wait one sync interval.
    Then collect and freeze what set-up built (prices, pre-built jobs,
    what tracing left), so no collection in the window scans it."""
    if _cache_entries(cache_dir) - entries_before:
        svc.sleep_until(time.time() + svc.worker.tune_sync_interval_s + 1.0)
    gc.collect()
    gc.freeze()


def _devices(cell: dict, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform "
                         f"{devs[0].platform!r})")
        if len(devs) != int(cell["chips"]):
            raise NoChip(f"the cell asks for {cell['chips']} chip(s); JAX "
                         f"found {len(devs)}")
    return devs


class _Profiler:
    """The device trace of the window (``--trace 1``), Python tracer off."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if not self.on:
            return None
        import jax

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return trace_mod.reduce(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _closed(svc, wl, run, seconds, prof, t_process, settle):
    """Closed loop; returns ``(jobs due, kept (instrument, jid) pairs)``."""
    sample = wl.sampled_instruments()
    warm = wl.sweep(0, "warm")
    with _annotate("bench.enqueue"):
        svc.queue.enqueue_many(warm)
    if not svc.wait([r.id for r in warm], 1200):
        raise serve.BackendFailed("the warm-up sweep did not complete")
    settle()
    kept = []

    def make_sweep(k):
        recs = wl.sweep(k)
        for i in sample:
            svc.keep.add(recs[i].id)
            kept.append((int(i), recs[i].id))
        return recs
    feeder = serve.Feeder(svc, make_sweep, len(wl.instruments),
                          int(wl.traffic["queued_sweeps"]))
    prof.start()
    feeder.start()
    svc.sleep_until(time.time() + float(wl.traffic["lead_in_s"]))
    # The window opens just before the first completion after the
    # lead-in, so every run starts at the same phase of the pipeline.
    armed, n0 = time.time(), len(svc.order)
    while True:
        svc.pump()
        fresh = [svc.done[j] for j in svc.order[n0:] if svc.done[j] > armed]
        if fresh:
            break
        time.sleep(0.001)
    t0 = min(fresh) - 1e-3
    ann = _annotate("bench.window")
    ann.__enter__()
    c0, k0 = svc.counters(), _Compiles.count
    t1 = t0 + seconds
    with _annotate("bench.wait"):
        svc.sleep_until(t1)
    c1, k1 = svc.counters(), _Compiles.count
    ann.__exit__(None, None, None)
    run.trace = prof.stop()
    feeder.stop()
    due = list(feeder.jobs)
    svc.wait(due, DRAIN_WAIT_S)
    done = [j for j in due if j in svc.done and t0 < svc.done[j] <= t1]
    run.window_jobs = len(done)
    run.backtests = len(done) * wl.combos
    run.counters = serve.counter_deltas(c1, c0)
    run.setup_s = t0 - t_process
    run.window = (t0, t1)
    say(f"{feeder.sweeps} sweeps enqueued, {len(done)} jobs completed in "
        f"the window, {k1 - k0} compile(s) in the window")
    in_window = set(done)
    return due, [(i, j) for i, j in kept if j in in_window]


def _warm_open(svc, wl):
    """Every instrument once (pages and panels resident), then one batch
    of each size the worker can take, so no shape compiles later."""
    per_poll = int(wl.config["worker"]["jobs_per_chip"]["open"])
    first = [wl.record(f"warm.{i}", inst)
             for i, inst in enumerate(wl.instruments)]
    svc.queue.enqueue_many(first)
    if not svc.wait([r.id for r in first], 1200):
        raise serve.BackendFailed("the warm-up jobs did not complete")
    for n in range(1, per_poll + 1):
        recs = [wl.record(f"warm{n}.{i}", wl.instruments[i])
                for i in range(n)]
        svc.queue.enqueue_many(recs)
        if not svc.wait([r.id for r in recs], 600):
            raise serve.BackendFailed(f"warm-up batch of {n} did not "
                                      "complete")


def _open(svc, wl, run, seconds, prof, t_process, settle):
    """Open loop; returns ``(jobs due in the window, kept pairs)``."""
    _warm_open(svc, wl)
    off, picks = wl.schedule(seconds)
    n_lead = int(np.sum(off < 0))
    recs = [wl.record(f"o{k}", wl.instruments[int(p)])
            for k, p in enumerate(picks)]
    sampled = wl.sampled_jobs(len(recs) - n_lead, n_lead)
    kept = [(int(picks[k]), recs[k].id) for k in sampled]
    svc.keep.update(j for _, j in kept)
    settle()
    lead = float(wl.traffic["lead_in_s"])
    t0 = time.time() + lead + 0.25
    due = t0 + off
    arrivals = serve.Arrivals(svc, due, recs)
    prof.start()
    arrivals.start()
    svc.sleep_until(t0)
    ann = _annotate("bench.window")
    ann.__enter__()
    c0, k0 = svc.counters(), _Compiles.count
    t1 = t0 + seconds
    with _annotate("bench.wait"):
        svc.sleep_until(t1)
    c1, k1 = svc.counters(), _Compiles.count
    ann.__exit__(None, None, None)
    run.trace = prof.stop()
    window = [k for k in range(n_lead, len(recs))]
    ids = [recs[k].id for k in window]
    svc.wait(ids, DRAIN_WAIT_S)
    arrivals.stop()
    lat = np.asarray([svc.done[recs[k].id] - due[k]
                      if recs[k].id in svc.done else math.inf
                      for k in window])
    run.latencies = lat
    run.window_jobs = int(np.isfinite(lat).sum())
    run.backtests = run.window_jobs * wl.combos
    run.queue_waits = [svc.queue_wait[j] for j in ids if j in svc.queue_wait]
    run.counters = serve.counter_deltas(c1, c0)
    run.setup_s = t0 - t_process
    run.window = (t0, t1)
    late = arrivals.lateness[n_lead:]
    fin = lat[np.isfinite(lat)]
    say(f"{len(window)} jobs due in the window at "
        f"{wl.traffic['rate_per_s']}/s; latency median "
        f"{np.median(fin) if fin.size else float('nan'):.6f} s over "
        f"{fin.size} jobs; generator late by median "
        f"{np.median(late):.6f} s, max {late.max():.6f} s; "
        f"{k1 - k0} compile(s) in the window")
    return ids, kept


def execute(cell: dict, config: dict, traffic_cfg: dict, *, seed: int,
            seconds: float, trace: bool, t_process: float,
            require_chip: bool = True, backend=None) -> dict:
    """Run the cell once and return the result line (a dict)."""
    devs = _devices(cell, require_chip)
    from distributed_backtesting_exploration_tpu.tune import compile_cache

    cache_dir = compile_cache.configure(min_compile_time_s=0.0)
    entries_before = _cache_entries(cache_dir)
    _Compiles.watch()
    wl = traffic.Workload(config, traffic_cfg, seed)
    family = cell_mod.reference(config["strategy"])
    run = Run(cell=cell, config=config, seconds=float(seconds),
              loop=wl.loop, combos=wl.combos, bars=wl.bars, family=family,
              axes=wl.axes, device_kind=devs[0].device_kind)
    prof = _Profiler(trace)
    svc = serve.Service(config, wl.loop, backend=backend)
    pauses = _GcPauses()
    watch = _StallWatch()
    watch.start()
    backend_error = None
    due, kept = [], []
    loop = _closed if wl.loop == "closed" else _open
    try:
        due, kept = loop(svc, wl, run, seconds, prof, t_process,
                         lambda: _settle(svc, cache_dir, entries_before))
    except serve.BackendFailed as e:
        backend_error = str(e)
        say(f"FAILED: {e}")
    finally:
        watch.stop()
        pauses.close()
        gc.unfreeze()
    if run.window is not None:
        say(pauses.report(*run.window))
        say(watch.report(*run.window))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    failures = svc.failures()
    missing = sum(1 for j in due if j not in svc.done)
    results = dict(svc.kept)
    svc.close()
    tally = check.Tally()
    refs: dict = {}
    t_ref = time.perf_counter()
    for inst, jid in kept:
        blob = results.get(jid)
        if blob is None:
            missing += 1
            continue
        if inst not in refs:
            refs[inst] = family.sweep(wl.legs(wl.instruments[inst]),
                                      wl.axes, cost=wl.cost,
                                      periods_per_year=wl.ppy)
        tally.add(refs[inst], _decode(blob))
    say(f"compared {tally.jobs} answers ({tally.cells} cells, "
        f"{len(refs)} instruments) in {time.perf_counter() - t_ref:.3f} s;"
        f" worst job {tally.worst_job_share:.6f}")
    nums = check.numbers(missing=missing, failed=failures, tally=tally,
                         limits=config["correct"])
    correct = backend_error is None and check.verdict(nums, tally)
    metrics = {}
    if backend_error is None:
        for spec in cell_mod.metric_specs(cell["name"], trace):
            value = cell_mod.reader(spec["name"])(run)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    line = {"correct": bool(correct), "attempted": len(due),
            "failed": int(missing + failures), "metrics": metrics,
            "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.idle_gaps}
        mods = sorted(run.trace.module_s.items(), key=lambda kv: -kv[1])
        say(f"device seconds per program: {mods[:12]}")
    line["checks"] = nums
    for name, v in nums.items():
        say(f"check {name} {v['value']} limit {v['limit']}")
    return line


def _decode(blob: bytes) -> np.ndarray | None:
    """A result block as ``(9, P)`` float64; None when it is malformed."""
    from distributed_backtesting_exploration_tpu.rpc import wire

    try:
        return np.stack([np.asarray(f, np.float64)
                         for f in wire.metrics_from_bytes(blob)])
    except ValueError:
        return None
