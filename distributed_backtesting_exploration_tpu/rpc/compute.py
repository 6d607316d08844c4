"""Worker compute backends: the slot the reference filled with a sleep.

The reference's worker pushes each job batch to an OS thread that sleeps one
second per job (reference ``src/worker/process.rs:13-29``, acknowledged as a
stub in reference ``README.md:84``). Here the same seam — a backend consuming
job batches and yielding completions — is filled by the fused JAX sweep
kernel; fake backends preserve the seam for control-plane tests exactly as
the stub's isolation suggested (SURVEY.md §4).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Iterable, NamedTuple, Protocol

import numpy as np

from . import backtesting_pb2 as pb
from . import wire
from .. import obs
from ..obs import flight as obs_flight
from ..utils import data as data_mod

log = logging.getLogger("dbx.compute")

_DEFAULT_CACHE_MB = 256


def cache_max_bytes() -> int:
    """Worker panel-cache budget (per level), read lazily — import-time
    env capture would pin the knob before tests/operators can set it."""
    return int(float(os.environ.get("DBX_PANEL_CACHE_MB",
                                    _DEFAULT_CACHE_MB)) * 1024 * 1024)


class PanelCache:
    """Two-level digest-keyed panel cache (dispatch by digest, worker side).

    The dispatcher content-addresses every panel (``JobSpec.panel_digest``)
    and, once a worker generation has received the bytes, ships
    digest-only jobs. This cache is what makes that hit cheap end to end:

    - **host level**: decoded :class:`~..utils.data.OHLCV` panels — a hit
      skips the wire decode entirely;
    - **device level**: the panel's stacked ``(5, T)`` field block already
      resident on the accelerator — a hit additionally skips the
      host->device transfer (group stacking then runs device-side);
    - **page level** (:attr:`pages`, ragged paged batching): field data as
      fixed-size T-pages in one device pool keyed by page CONTENT — an
      append-extended panel reuses all of its base's full pages and
      overlapping histories share pages across digests, where the block
      level would duplicate the whole ``(5, T)`` history per digest.

    The first two levels are LRU-bounded by approximate bytes
    (``DBX_PANEL_CACHE_MB``, default 256 per level); the page pool by
    ``DBX_PAGE_POOL_MB``. Eviction is not an error: the worker recovers
    a digest-only miss through the dispatcher's ``FetchPayload`` RPC, and
    a pool-rejected group falls back to the dense stack path.
    Thread-safe — the worker's control thread probes/fills the host level
    while the compute thread serves from all levels.
    """

    def __init__(self, max_bytes: int | None = None,
                 registry: "obs.Registry | None" = None):
        from .panel_store import ByteLRU

        self.max_bytes = (cache_max_bytes() if max_bytes is None
                          else int(max_bytes))
        self._lock = threading.Lock()
        self._pages = None
        # Both levels ride the ONE eviction/accounting implementation the
        # dispatcher's blob store uses (panel_store.ByteLRU); only the
        # pricing differs (decoded array nbytes vs caller-supplied device
        # block size).
        self._series = ByteLRU(self.max_bytes, self._nbytes)
        self._device = ByteLRU(self.max_bytes)   # put() passes nbytes
        reg = registry or obs.get_registry()
        self._reg = reg
        self._c_hits = {
            lvl: reg.counter("dbx_panel_cache_hits_total",
                             help="panel-cache hits by level "
                                  "(host=decode skipped, device=h2d "
                                  "skipped too)", level=lvl)
            for lvl in ("host", "device")}
        self._c_misses = {
            lvl: reg.counter("dbx_panel_cache_misses_total",
                             help="panel-cache misses by level",
                             level=lvl)
            for lvl in ("host", "device")}
        self._g_bytes = reg.gauge(
            "dbx_panel_cache_bytes",
            help="approximate bytes resident in the worker panel cache "
                 "(host + device levels)")

    @staticmethod
    def _nbytes(arrays) -> int:
        return int(sum(getattr(a, "nbytes", 0) for a in arrays))

    def _publish_bytes(self) -> None:
        self._g_bytes.set(self._series.bytes + self._device.bytes)

    def contains_series(self, digest: str) -> bool:
        """Non-counting probe (the control thread's pre-dispatch check —
        a probe must not inflate the hit rate the compute path reports)."""
        with self._lock:
            return digest in self._series

    def get_series(self, digest: str):
        with self._lock:
            s = self._series.get(digest)
        if s is not None:
            self._c_hits["host"].inc()
        else:
            self._c_misses["host"].inc()
        return s

    def put_series(self, digest: str, series) -> None:
        with self._lock:
            self._series.put(digest, series)
            self._publish_bytes()

    def get_device(self, digest: str):
        with self._lock:
            d = self._device.get(digest)
        if d is not None:
            self._c_hits["device"].inc()
        else:
            self._c_misses["device"].inc()
        return d

    def put_device(self, digest: str, block, nbytes: int) -> None:
        """Cache a device-resident field block. ``nbytes`` is passed in
        (not read off the array): a just-launched device_put's .nbytes is
        known host-side without forcing a sync."""
        with self._lock:
            self._device.put(digest, block, nbytes)
            self._publish_bytes()

    @property
    def pages(self):
        """Third cache level: the device page pool (ragged paged
        batching), created lazily so workers that never take the paged
        route (mesh workers, pre-digest dispatchers, DBX_PAGED=0) do not
        allocate it."""
        with self._lock:
            if self._pages is None:
                from .page_pool import PagePool

                self._pages = PagePool(registry=self._reg)
            return self._pages

    def stats(self) -> dict:
        with self._lock:
            out = {"host_panels": len(self._series),
                   "host_bytes": self._series.bytes,
                   "device_panels": len(self._device),
                   "device_bytes": self._device.bytes,
                   "max_bytes": self.max_bytes}
            pages = self._pages
        if pages is not None:
            out["page_pool"] = pages.stats()
        return out

    def top_digests(self, k: int = 8) -> list[dict]:
        """The top-``k`` resident panels by byte size across the host +
        device levels — the fleet telemetry frame's digest SKETCH
        (12-hex prefixes + byte sizes, never the full key list: a
        thousand-panel cache must not ride every poll)."""
        sizes: dict[str, int] = {}
        with self._lock:
            for key, nb in self._series.sizes() + self._device.sizes():
                sizes[key] = sizes.get(key, 0) + int(nb)
        top = sorted(sizes.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
        return [{"d": str(d)[:12], "b": nb} for d, nb in top]


class Completion:
    """One finished job: id + packed DBXM metrics + compute seconds.

    ``trace_id`` echoes the job's dispatcher-minted trace (JobSpec.trace_id)
    so the report leg and the CompleteItem wire echo stay stitchable;
    empty for jobs enqueued by a pre-tracing dispatcher."""

    __slots__ = ("job_id", "metrics", "elapsed_s", "trace_id")

    def __init__(self, job_id: str, metrics: bytes, elapsed_s: float,
                 trace_id: str = ""):
        self.job_id = job_id
        self.metrics = metrics
        self.elapsed_s = elapsed_s
        self.trace_id = trace_id


class _ScenarioJob:
    """Per-scenario identity inside a coalesced spec-batch job: collect()
    and the obs span helpers read id/grid/trace ATTRIBUTES off whatever
    object rides the pending entry, so a K-spec batch completes as K
    ordinary per-scenario results without K JobSpec protos ever existing
    worker-side. ``grid`` aliases the carrier JobSpec's shared grid map
    (every batch member swept the same grid by construction)."""

    __slots__ = ("id", "grid", "trace_id", "parent_span_id")

    def __init__(self, job_id: str, grid, trace_id: str,
                 parent_span_id: str):
        self.id = job_id
        self.grid = grid
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id


class ComputeBackend(Protocol):
    def process(self, jobs: Iterable[pb.JobSpec]) -> list[Completion]:
        """Run a job batch to completion (synchronous, CPU/TPU-bound)."""
        ...

    @property
    def chips(self) -> int:
        """Device count to advertise to the dispatcher."""
        ...

    # Backends may additionally expose a two-phase pipeline:
    #   submit(jobs) -> opaque handle   (dispatch work, return immediately)
    #   collect(handle) -> [Completion] (block for results)
    # The worker runs submit and collect on separate threads of a bounded
    # pipeline (DBX_PIPELINE, round 14) when both methods exist — the
    # decode -> H2D -> compute double-buffering SURVEY.md §2.3 (PP row)
    # prescribes against the reference's serial loop (reference
    # src/worker/process.rs:21-25) — and calls the optional
    #   prefetch(jobs) -> int  (stage inputs early; best-effort)
    # hook from its CONTROL thread for batches still queued behind the
    # pipeline (DBX_PREFETCH).


def _stack_field_ragged(series_list, t_max: int,
                        field: str = "close") -> np.ndarray:
    """Single-column ragged stack with repeat-last padding to ``t_max`` bars.

    Repeat-last padding is load-bearing: pad bars earn exactly zero return
    and hold the final position, so the kernels' reductions over the padded
    width equal the unpadded ones (see ops.fused). Shared by the
    single-asset and pairs submit paths so the discipline cannot diverge.
    (For non-close columns — high/low channels, volume — the repeated last
    value changes nothing either: pad-bar positions never reach a metric.)
    """
    out = np.empty((len(series_list), t_max), np.float32)
    for i, s in enumerate(series_list):
        a = np.asarray(getattr(s, field), np.float32)
        out[i, :a.shape[0]] = a
        out[i, a.shape[0]:] = a[-1]
    return out


class _FusedSpec(NamedTuple):
    """One fused-kernel routing row (see ``_FUSED_STRATEGIES``)."""

    axes: set               # required grid axes, exactly
    window_axes: tuple      # axes whose values must be integral bar counts
    run: Callable           # (*field_arrays, grid, cost, ppy, t_real) -> Metrics
    table_axes: tuple | None = None   # axes sizing the selection table
    fields: tuple = ("close",)        # OHLCV columns the kernel consumes


class _TimeshardSpec(NamedTuple):
    """One time-sharded (long-context) routing row.

    Maps a strategy to its ``parallel.timeshard`` composed backtest: the
    positional parameter order of the sharded function, the OHLCV columns
    it consumes, and whether its signal head needs a window-sized halo
    (EMA-state families carry O(1) state, so their windows are not bounded
    by the per-chip block length)."""

    params: tuple           # positional param axes, in the fn's order
    fields: tuple           # OHLCV columns the backtest consumes
    fn_name: str            # attribute in parallel.timeshard
    halo_bound: bool = True  # window must fit one per-chip block


def _start_result_copy(m, *, donate: bool = True):
    """Stack the 9 metric fields on device and begin the async d2h copy.

    ``donate=False`` opts a caller out of the TPU buffer donation — the
    streaming-append path must, because ``recurrent.finalize``'s outputs
    may alias buffers the stored carry checkpoint still owns."""
    stacked = _stack_metrics(*m, donate=donate)
    try:
        stacked.copy_to_host_async()
    except AttributeError:
        pass   # non-jax array (already host-resident)
    return stacked


_STACK_METRICS_CACHE: dict = {}


def _stack_metrics(*fields, donate: bool = True):
    """Stack 9 metric fields into one device array under jit (one transfer).

    On TPU the inputs are DONATED: the per-field sweep outputs hand
    their buffers to the stacked block, so a deep pipeline holds one
    result block per in-flight batch instead of block + 9 donors — the
    donated-buffer half of the round-14 async-collect contract. CPU/GPU
    skip donation (XLA there may not consume it and jax warns per call).
    """
    import jax

    key = "fn"
    donate = donate and jax.default_backend() == "tpu"
    if donate:
        key = "fn_donate"
    fn = _STACK_METRICS_CACHE.get(key)
    if fn is None:
        import jax.numpy as jnp

        fn = _STACK_METRICS_CACHE[key] = jax.jit(
            lambda *fs: jnp.stack(fs),
            donate_argnums=tuple(range(9)) if donate else ())
    return fn(*fields)


_TOPK_FN_CACHE: dict = {}


def _topk_reduce(m, metric: str, k: int):
    """On-device top-k: ``(N, P)`` Metrics -> ``((N, k) idx, (N, k) Metrics)``.

    Rows are ranked by ``metric`` in the metric's own direction
    (``metric_sign``), NaN rows last. Runs under jit on whatever sharding
    the sweep produced (the param axis is unsharded in every backend path,
    so ``top_k``/``take_along_axis`` stay chip-local) — the reduction is
    the "move scalars, not matrices" half of the north star's per-chip
    batching story (``JobSpec.top_k``).
    """
    import jax

    from ..ops.metrics import Metrics, metric_sign

    key = (metric, int(k))
    fn = _TOPK_FN_CACHE.get(key)
    if fn is None:
        import jax.numpy as jnp

        sign = float(metric_sign(metric))
        pos = Metrics._fields.index(metric)

        def f(*fields):
            score = fields[pos] * sign
            score = jnp.where(jnp.isnan(score), -jnp.inf, score)
            _, idx = jax.lax.top_k(score, k)
            return idx, [jnp.take_along_axis(f_, idx, axis=1)
                         for f_ in fields]

        fn = _TOPK_FN_CACHE[key] = jax.jit(f)
    idx, sel = fn(*m)
    try:
        idx.copy_to_host_async()
    except AttributeError:
        pass
    return idx, Metrics(*sel)


class JaxSweepBackend:
    """The real engine: decode OHLCV bytes, run the fused sweep, pack metrics.

    Jobs in a batch that share (strategy, grid, n_bars) are stacked into one
    (tickers x params) device call — the per-chip batching the north star
    prescribes — instead of being looped one by one. The submit/collect
    split lets the worker overlap batch N+1's decode/H2D/compute with batch
    N's result transfer (SURVEY.md §2.3 PP row; the reference's serial loop
    at src/worker/process.rs:21-25 is the anti-pattern).
    """

    def __init__(self, *, param_chunk: int | None = None,
                 use_fused: bool | None = None,
                 use_mesh: bool | None = None):
        import jax  # deferred: workers decide platform via env/config

        self._jax = jax
        self.param_chunk = param_chunk
        # local_devices, not devices: under jax.distributed a process sees
        # every host's chips in jax.devices(), but a WORKER is one process
        # on one host — it can only feed (and should only advertise) its
        # own chips. Cross-host scale-out is the dispatcher's job.
        self._devices = jax.local_devices()
        from ..ops import fused as fused_ops

        # The fused Pallas kernel is compiled-TPU only; its interpret mode
        # is far slower than the generic XLA path on CPU. The auto default
        # follows the kernels' own platform helper, which raises on a
        # platform that is neither (no silent fallback off the device).
        on_tpu = not fused_ops.interpret_default()
        if use_fused is None:
            use_fused = on_tpu
        self.use_fused = use_fused
        # Multi-chip workers shard every job group's ticker axis over a 1-D
        # mesh of the local chips (advertising N chips while computing on
        # one would leave N-1 idle). Defaults on for real multi-chip TPU
        # hosts; tests opt in on the virtual CPU mesh.
        if use_mesh is None:
            use_mesh = len(self._devices) > 1 and on_tpu
        self._mesh = None
        self._mesh_fns: dict = {}
        self._time_mesh_cache = None
        if use_mesh and len(self._devices) > 1:
            from ..parallel import sharding as sharding_mod

            self._mesh = sharding_mod.make_mesh(self._devices)
        # Observability (DESIGN.md "Observability"): per-phase attribution
        # of the decode -> submit -> device-drain pipeline, kernel wall
        # keyed by route:strategy (the live counterpart of bench.py's
        # roofline stages), and the jit compile-vs-execute split (first
        # call on a signature = compile-inclusive "cold").
        reg = obs.get_registry()
        self._obs = reg
        self._h_decode = reg.histogram(
            "dbx_compute_decode_seconds",
            help="OHLCV wire decode wall per job group")
        self._c_decode_bytes = reg.counter(
            "dbx_compute_decode_bytes_total",
            help="OHLCV payload bytes decoded")
        self._h_collect = reg.histogram(
            "dbx_compute_collect_seconds",
            help="device drain + d2h wait per pending group")
        self._c_d2h_bytes = reg.counter(
            "dbx_compute_d2h_bytes_total",
            help="result bytes copied device->host")
        self._c_backtests = reg.counter(
            "dbx_backtests_total", help="(ticker x param) combos computed")
        self._bt_rate = obs.StepTimer(reg.gauge(
            "dbx_compute_backtests_per_sec",
            help="combos/s since backend start"))
        self._h_jit = {
            phase: reg.histogram(
                "dbx_jit_call_seconds",
                help="mesh-fn dispatch wall: cold includes trace+compile, "
                     "warm is async launch only", phase=phase)
            for phase in ("cold", "warm")}
        self._kern_h: dict = {}    # (strategy, route, cold) -> Histogram
        self._seen_cold: set = set()
        # Live fused-kernel substrate defaults (epilogue / table / lanes
        # cap): an info-style gauge whose LABELS carry the values, so
        # /metrics, /stats.json, GetStats obs_json and `obs.dump` all show
        # per-worker which substrate is serving without reading logs
        # (DESIGN.md "Roofline accounting"). Resolved once here — the same
        # env validation the first sweep would hit, surfaced at backend
        # construction instead of mid-batch.
        self._fused_ops = fused_ops
        # Ragged paged panel batching (round 10): fused groups assemble
        # from the device page pool (PanelCache.pages) through per-job
        # page tables instead of dense per-length stacks. Meshless fused
        # workers only — the mesh path needs explicit shardings on its
        # device_put (same boundary as the device block cache).
        # DBX_PAGED=0 is the kill switch.
        self.use_paged = (self.use_fused and self._mesh is None
                          and fused_ops.paged_enabled())
        # Padding-waste observability: bars materialized ONLY to batch
        # (dense = repeat-last stacks padded to the group/bucket max;
        # paged = in-page pad of newly uploaded partial tail pages —
        # bounded by one page per ticker).
        _pad_help = ("panel pad bars materialized for batching, by "
                     "execution path (dense = stacks padded to the group "
                     "max; paged = in-page pad of uploaded tail pages)")
        self._c_pad_bars = {
            "dense": reg.counter("dbx_pad_bars_total", help=_pad_help,
                                 path="dense"),
            "paged": reg.counter("dbx_pad_bars_total", help=_pad_help,
                                 path="paged")}
        reg.gauge("dbx_fused_substrate_info",
                  help="constant 1; labels carry the live fused-kernel "
                       "substrate defaults (epilogue/table/lanes)",
                  **fused_ops.substrate_defaults()).set(1)
        # (strategy, epilogue, table) -> Counter: which substrate served
        # each fused job group (the per-group twin of the info gauge).
        self._substrate_counters: dict = {}
        # jit caches per input SHAPE, not just per program key: a cached
        # mesh fn hit with a new (rows, bars) signature recompiles for
        # seconds and must not be attributed as "warm" async launch.
        self._seen_shapes: set = set()
        # Dispatch by digest (worker half): decoded-panel + device-block
        # cache keyed by JobSpec.panel_digest, and the FetchPayload hook
        # the Worker installs (compute-thread recovery for the
        # evicted-between-poll-and-decode race).
        self.panel_cache = PanelCache(registry=reg)
        self.payload_fetcher: Callable[[str], bytes] | None = None
        # Streaming appends (JobSpec.append_*): digest-keyed carry
        # checkpoints so an appended ΔT-bar slice advances a finished
        # sweep in O(ΔT) instead of repricing T bars (streaming/).
        from ..streaming import CarryStore

        self.carry_store = CarryStore(registry=reg)
        self._c_append = {
            outcome: reg.counter(
                "dbx_worker_append_total",
                help="streaming append jobs served, by outcome "
                     "(carry_hit=O(ΔT) advance, full_reprice=checkpoint "
                     "miss fallback)", outcome=outcome)
            for outcome in ("carry_hit", "full_reprice")}
        # Scenario megakernel route accounting: which path served each
        # scenario job. `fused` = in-trace regeneration inside the sweep
        # launch (panel never in HBM); `materialized` = a concrete panel
        # was generated first — dispatcher-side (old capability / kill
        # switch / digestless base) or the worker's own in-process
        # fallback when the fused leg fails.
        self._c_scenario_route = {
            mode: reg.counter(
                "dbx_scenario_route_total",
                help="scenario jobs served, by route (fused=in-trace "
                     "regeneration, materialized=concrete panel)",
                mode=mode)
            for mode in ("materialized", "fused")}
        # Substrate autotuner (tune/, round 11): the schedule registry is
        # consulted per fused group submit — explicit arg > env > tuned
        # schedule > hardcoded default, so every existing override keeps
        # its exact semantics — and, under DBX_AUTOTUNE, first contact
        # with a (family, shape-bucket) measures the substrate
        # cross-product and persists the winner. The worker control loop
        # gossips new entries up (JobsRequest.schedule_json) and adopts
        # the merged fleet registry from GetStats, so the Nth worker
        # inherits the first worker's tuning without re-measuring.
        from .. import tune as tune_mod

        self._tune = tune_mod
        self.schedule_registry = tune_mod.ScheduleRegistry.open_default(
            registry=reg)
        self._autotuner = tune_mod.Autotuner(self.schedule_registry,
                                             registry=reg)
        self._platform = jax.default_backend()
        # First-contact memo: a (family, bucket) whose tune attempt found
        # no winner must not re-pay the measurement on every group.
        self._tuned_attempted: set = set()
        self._tuned_info_seen: set = set()
        # Construction-time tuned defaults: knobs that bind before any
        # group submit (the page pool's page size) apply through the
        # process-wide tuned default layer when the restored registry
        # holds a page_bars winner for this platform (deterministic pick:
        # most common value, ties to the smallest).
        pb_counts: dict = {}
        for e in self.schedule_registry.entries():
            if e["platform"] != self._platform:
                continue
            v = e["substrates"].get("page_bars")
            if v:
                pb_counts[v] = pb_counts.get(v, 0) + 1
        if pb_counts:
            pb_pick = sorted(pb_counts.items(),
                             key=lambda kv: (-kv[1], kv[0]))[0][0]
            fused_ops.set_tuned_defaults({"page_bars": pb_pick})

    def _evict_mesh_fn(self) -> None:
        """FIFO-evict the oldest compiled mesh fn AND its shape-signature
        memory: eviction discards the jit cache, so the rebuilt fn's first
        call recompiles and must count as "cold" again."""
        evicted = next(iter(self._mesh_fns))
        del self._mesh_fns[evicted]
        self._seen_shapes = {sk for sk in self._seen_shapes
                             if sk[0] != evicted}

    def _observe_submit(self, strategy: str, route: str, t0: float,
                        cold_key=None, group=None, bars=None,
                        combos=None) -> None:
        """Record a group's submit-side wall (group start -> kernels
        launched, decode included) into
        ``dbx_kernel_submit_seconds{kernel=route:strategy}``. ``cold_key``
        marks the first submission of a compile signature as
        phase="compile" (the jit compile-vs-execute split at group grain).

        With ``group`` given, the same interval is also emitted as a
        ``worker.compile`` / ``worker.execute`` span joined to every job's
        trace — the timeline analyzer's compile-vs-execute stage split
        (the decode span nests inside this interval and wins attribution
        for its sub-range). ``bars``/``combos`` ride the span as shape
        attrs so the cost-model drift plane (obs/costmodel.py) can score
        the measured wall against the op model's prediction."""
        dt = time.perf_counter() - t0
        cold = False
        if cold_key is not None:
            cold = cold_key not in self._seen_cold
            if cold:
                if len(self._seen_cold) > 4096:   # long-lived worker bound
                    self._seen_cold.clear()
                self._seen_cold.add(cold_key)
        hk = (strategy, route, cold)
        h = self._kern_h.get(hk)
        if h is None:
            h = self._kern_h[hk] = self._obs.histogram(
                "dbx_kernel_submit_seconds",
                help="per-group submit wall (decode + H2D + launch) by "
                     "route:strategy",
                kernel=f"{route}:{strategy}",
                phase="compile" if cold else "execute")
        h.observe(dt)
        if group is not None:
            pairs = obs.job_trace_pairs(group)
            if pairs:
                shape = {}
                if bars is not None:
                    shape["bars"] = int(bars)
                if combos is not None:
                    shape["combos"] = int(combos)
                obs.emit_span("worker.compile" if cold else "worker.execute",
                              time.time() - dt, dt, pairs=pairs,
                              kernel=f"{route}:{strategy}",
                              jobs=len(group), **shape)

    def _observe_substrates(self, strategy: str) -> None:
        """Count a fused group against the substrate set that served it
        (``dbx_fused_substrate_total{kernel,epilogue,table}``)."""
        subs = self._fused_ops.route_substrates(strategy)
        key = (strategy, subs["epilogue"], subs["table"])
        c = self._substrate_counters.get(key)
        if c is None:
            c = self._substrate_counters[key] = self._obs.counter(
                "dbx_fused_substrate_total",
                help="fused job groups served, by kernel and "
                     "epilogue/table substrate",
                kernel=strategy, **subs)
        c.inc()

    @property
    def accepts_scenario_batch(self) -> bool:
        """Capability the worker advertises on JobsRequest: this backend
        can regenerate scenario panels in-trace inside the fused sweep
        launch. Read per poll so flipping the ``DBX_SCENARIO_FUSED`` kill
        switch stops NEW spec batches immediately (already-leased
        batches still drain through the in-process materialized
        fallback)."""
        return self._fused_ops.scenario_fused_enabled()

    @property
    def chips(self) -> int:
        # Honest capacity advertising: a meshless backend computes every
        # group on ONE device, so a multi-chip host claiming all of them
        # would take leases it cannot parallelize; the mesh path advertises
        # the real fan-out.
        return len(self._devices) if self._mesh is not None else 1

    def telemetry(self) -> dict:
        """Capability flags + cache residency for the fleet telemetry
        frame (obs/fleet.py): counts and byte totals per cache level
        plus a bounded top-K digest sketch — the placement-scorer's
        future input (ROADMAP item 3: carry hits, page residency and a
        warm compile cache are exactly the stage costs it ranks)."""
        return {
            "caps": {"backend": "jax", "chips": self.chips,
                     "platform": self._platform,
                     "fused": bool(self.use_fused),
                     "mesh": self._mesh is not None,
                     "paged": bool(self.use_paged)},
            "caches": {
                "panel": self.panel_cache.stats(),
                "panel_topk": self.panel_cache.top_digests(),
                "carry": self.carry_store.stats(),
                "schedule_entries": len(
                    self.schedule_registry.entries()),
            },
        }

    # The longest history and the most table windows routed to a fused
    # kernel: every family compiles there for the v5e under ops.fused's
    # scoped-VMEM limit (tests/test_tpu_compile.py holds the cap).
    _FUSED_MAX_BARS = 8192
    _FUSED_MAX_WINDOWS = 128
    # Families whose rolling variance (``s2 - s1**2 / w`` on series-
    # centred sums) cancels when a window's mean sits far from the
    # series mean: in a ragged group (jobs of different lengths batched
    # together) the changed rounding order flipped up to 5 % of Sharpe
    # cells on the v5e against each job run alone (ROADMAP B7). Until the
    # variance is better conditioned, these group by exact length, so a
    # job's answer never depends on what it is batched with.
    _EXACT_LENGTH_STRATEGIES = frozenset(
        {"bollinger", "bollinger_touch", "vwap_reversion", "pairs"})
    # Device memory one launch may take: a third of the v5e's 16 GiB of
    # HBM, the rest left to the page pool and in-flight results. A
    # ticker costs at most _HBM_BYTES_PER_BAR per padded bar (the v5e
    # compiler's memory analysis of every fused family and pairs, at 16
    # x 8,192 bars x 128 windows and 500 x 1,260 x 40, peaks at ~2.8 KB)
    # plus _HBM_BYTES_PER_COMBO per grid combo (nine f32 metrics, padded
    # and copied once). submit() splits larger groups.
    _HBM_BUDGET = (16 << 30) // 3
    _HBM_BYTES_PER_BAR = 4096
    _HBM_BYTES_PER_COMBO = 64

    # Fused Pallas kernels per strategy, described by _FusedSpec rows.
    # "Table axes" are the ones whose distinct values size the kernel's
    # selection table (defaults to the integral window axes); MACD's signal
    # spans are per-lane decays, not a table dimension, so they must not
    # count toward the window cap. "Fields" are the OHLCV columns the kernel
    # consumes — only those reach the device. Eligibility and dispatch share
    # this table so they cannot drift.
    @staticmethod
    def _run_fused_sma(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_sma_sweep(
            close, np.asarray(grid["fast"]), np.asarray(grid["slow"]),
            t_real=t_real, cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_bollinger(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_bollinger_sweep(
            close, np.asarray(grid["window"]), np.asarray(grid["k"]),
            t_real=t_real, cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_bollinger_touch(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_bollinger_touch_sweep(
            close, np.asarray(grid["window"]), np.asarray(grid["k"]),
            t_real=t_real, cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_momentum(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_momentum_sweep(
            close, np.asarray(grid["lookback"]), t_real=t_real, cost=cost,
            periods_per_year=ppy)

    @staticmethod
    def _run_fused_donchian(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_donchian_sweep(
            close, np.asarray(grid["window"]), t_real=t_real, cost=cost,
            periods_per_year=ppy)

    @staticmethod
    def _run_fused_rsi(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_rsi_sweep(
            close, np.asarray(grid["period"]), np.asarray(grid["band"]),
            t_real=t_real, cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_macd(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_macd_sweep(
            close, np.asarray(grid["fast"]), np.asarray(grid["slow"]),
            np.asarray(grid["signal"]), t_real=t_real, cost=cost,
            periods_per_year=ppy)

    @staticmethod
    def _run_fused_trix(close, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_trix_sweep(
            close, np.asarray(grid["span"]), np.asarray(grid["signal"]),
            t_real=t_real, cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_donchian_hl(close, high, low, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_donchian_hl_sweep(
            close, high, low, np.asarray(grid["window"]), t_real=t_real,
            cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_stochastic(close, high, low, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_stochastic_sweep(
            close, high, low, np.asarray(grid["window"]),
            np.asarray(grid["band"]), t_real=t_real, cost=cost,
            periods_per_year=ppy)

    @staticmethod
    def _run_fused_keltner(close, high, low, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_keltner_sweep(
            close, high, low, np.asarray(grid["window"]),
            np.asarray(grid["k"]), t_real=t_real, cost=cost,
            periods_per_year=ppy)

    @staticmethod
    def _run_fused_obv(close, volume, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_obv_sweep(
            close, volume, np.asarray(grid["window"]), t_real=t_real,
            cost=cost, periods_per_year=ppy)

    @staticmethod
    def _run_fused_vwap(close, volume, grid, cost, ppy, t_real):
        from ..ops import fused
        return fused.fused_vwap_sweep(
            close, volume, np.asarray(grid["window"]),
            np.asarray(grid["k"]), t_real=t_real, cost=cost,
            periods_per_year=ppy)

    _FUSED_STRATEGIES = {
        "sma_crossover": _FusedSpec({"fast", "slow"}, ("fast", "slow"),
                                    _run_fused_sma),
        "bollinger": _FusedSpec({"window", "k"}, ("window",),
                                _run_fused_bollinger),
        "bollinger_touch": _FusedSpec({"window", "k"}, ("window",),
                                      _run_fused_bollinger_touch),
        "momentum": _FusedSpec({"lookback"}, ("lookback",),
                               _run_fused_momentum),
        "donchian": _FusedSpec({"window"}, ("window",), _run_fused_donchian),
        "donchian_hl": _FusedSpec({"window"}, ("window",),
                                  _run_fused_donchian_hl,
                                  fields=("close", "high", "low")),
        "rsi": _FusedSpec({"period", "band"}, ("period",), _run_fused_rsi),
        "stochastic": _FusedSpec({"window", "band"}, ("window",),
                                 _run_fused_stochastic,
                                 fields=("close", "high", "low")),
        "keltner": _FusedSpec({"window", "k"}, ("window",),
                              _run_fused_keltner,
                              fields=("close", "high", "low")),
        "macd": _FusedSpec({"fast", "slow", "signal"},
                           ("fast", "slow", "signal"), _run_fused_macd,
                           table_axes=("fast", "slow")),
        "trix": _FusedSpec({"span", "signal"}, ("span", "signal"),
                           _run_fused_trix, table_axes=("span",)),
        "vwap_reversion": _FusedSpec({"window", "k"}, ("window",),
                                     _run_fused_vwap,
                                     fields=("close", "volume")),
        "obv_trend": _FusedSpec({"window"}, ("window",), _run_fused_obv,
                                fields=("close", "volume")),
    }

    # Time-sharded long-context backtests (parallel.timeshard): the route
    # for jobs whose bar count exceeds the fused kernels' VMEM cap on a
    # meshed worker whose ticker axis cannot fill the chips. Each strategy
    # maps to its composed sharded backtest; parameters are per-combo
    # statics (halo sizes and EMA decays bake into the compiled program),
    # so a grid sweeps as one jitted program with one sub-backtest per
    # combo. Fields/axes mirror _FUSED_STRATEGIES so routing cannot drift.
    _TIMESHARD_STRATEGIES = {
        "sma_crossover": _TimeshardSpec(("fast", "slow"), ("close",),
                                        "sharded_sma_backtest"),
        "bollinger": _TimeshardSpec(("window", "k"), ("close",),
                                    "sharded_bollinger_backtest"),
        "bollinger_touch": _TimeshardSpec(("window", "k"), ("close",),
                                          "sharded_bollinger_touch_backtest"),
        "momentum": _TimeshardSpec(("lookback",), ("close",),
                                   "sharded_momentum_backtest"),
        "donchian": _TimeshardSpec(("window",), ("close",),
                                   "sharded_donchian_backtest"),
        "donchian_hl": _TimeshardSpec(("window",), ("close", "high", "low"),
                                      "sharded_donchian_hl_backtest"),
        "rsi": _TimeshardSpec(("period", "band"), ("close",),
                              "sharded_rsi_backtest", halo_bound=False),
        "stochastic": _TimeshardSpec(("window", "band"),
                                     ("close", "high", "low"),
                                     "sharded_stochastic_backtest"),
        "keltner": _TimeshardSpec(("window", "k"), ("close", "high", "low"),
                                  "sharded_keltner_backtest"),
        "macd": _TimeshardSpec(("fast", "slow", "signal"), ("close",),
                               "sharded_macd_backtest", halo_bound=False),
        "trix": _TimeshardSpec(("span", "signal"), ("close",),
                               "sharded_trix_backtest", halo_bound=False),
        "vwap_reversion": _TimeshardSpec(("window", "k"),
                                         ("close", "volume"),
                                         "sharded_vwap_backtest"),
        "obv_trend": _TimeshardSpec(("window",), ("close", "volume"),
                                    "sharded_obv_backtest"),
    }

    # Every grid combo compiles its own sub-backtest (windows are static
    # halo sizes); cap the per-group program count so a huge grid cannot
    # spend minutes in XLA before its first result.
    _TIMESHARD_MAX_COMBOS = 128

    # Walk-forward routes to the fused-train two-phase split only when the
    # grid is large enough for the train sweep to dominate; below this the
    # generic single-program walk_forward measured faster (bench.py:
    # 11.5M/s generic vs 5.5M/s fused at P=400 on a v5e chip).
    _WF_FUSED_MIN_COMBOS = 512

    def _timeshard_window_reason(self, wins, n_combos: int, t_min: int, *,
                                 halo_bound: bool = True,
                                 what: str = "window") -> str | None:
        return _timeshard_window_reason(
            wins, n_combos, t_min, self._mesh.devices.size,
            halo_bound=halo_bound, what=what)

    def _timeshard_reason(self, job, axes, lengths) -> str | None:
        """None when a long-context group can route to the time-sharded
        backtests; otherwise why it stays on the generic path."""
        return timeshard_route_reason(job.strategy, axes, lengths,
                                      self._mesh.devices.size)

    def _time_mesh(self):
        """1-D mesh over the SAME local chips with the TIME axis name
        (the worker's ticker mesh re-labeled for bar-axis sharding)."""
        if self._time_mesh_cache is None:
            from jax.sharding import Mesh

            from ..parallel import timeshard

            self._time_mesh_cache = Mesh(
                self._mesh.devices, (timeshard.TIME_AXIS,))
        return self._time_mesh_cache

    def _submit_timeshard_groups(self, group, series, lengths, t0, axes):
        """Long-context jobs: shard the BAR axis over the local chip mesh.

        The submit path for groups whose history exceeds the fused VMEM
        cap but whose ticker count cannot fill the mesh — instead of
        demoting to a single device's generic path, each grid combo runs
        the composed blockwise backtest from ``parallel.timeshard``
        (distributed cumsums / EMA carries / transition-map folds over
        ICI), so one history longer than any chip's memory uses every
        chip. Histories pad right with repeat-last values to a mesh
        multiple and pass their real length (``t_real``) so pad bars are
        dead in every metric. Returns one pending entry per length
        subgroup (ragged groups cannot share one padded panel).
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops.metrics import Metrics
        from ..parallel import timeshard

        job0 = group[0]
        fam = self._TIMESHARD_STRATEGIES[job0.strategy]
        fn = getattr(timeshard, fam.fn_name)
        tmesh = self._time_mesh()
        n_dev = tmesh.devices.size
        cost = float(job0.cost)
        ppy = int(job0.periods_per_year or 252)
        # DBXM column order IS product_grid order — the shared helper
        # keeps this path and the slice worker on one contract.
        combos = timeshard_combos(job0.strategy, axes)

        subgroups: dict[int, list[int]] = {}
        for i, t in enumerate(lengths):
            subgroups.setdefault(int(t), []).append(i)

        pending = []
        for t, idxs in sorted(subgroups.items()):
            T_pad = -(-t // n_dev) * n_dev
            sub_jobs = [group[i] for i in idxs]
            arrays = [_stack_field_ragged([series[i] for i in idxs], T_pad,
                                          f)
                      for f in fam.fields]
            sharded = [jax.device_put(
                a, NamedSharding(tmesh, P(None, timeshard.TIME_AXIS)))
                for a in arrays]
            t_real = None if t == T_pad else t
            key = (("timeshard",) + self._group_key(job0, axes)
                   + (t, T_pad))
            run = self._mesh_fns.get(key)
            if run is None:
                def run(*arrs, _tr=t_real):
                    ms = [fn(tmesh, *arrs, *cmb, cost=cost,
                             periods_per_year=ppy,
                             axis_name=timeshard.TIME_AXIS, t_real=_tr)
                          for cmb in combos]
                    return Metrics(*(jnp.stack(cols, axis=-1)
                                     for cols in zip(*ms)))

                run = jax.jit(run)
                if len(self._mesh_fns) >= self._MESH_FN_CAP:
                    self._evict_mesh_fn()
                self._mesh_fns[key] = run
            m = run(*sharded)
            pending.append(self._finish_group(sub_jobs, m, t0,
                                              len(sub_jobs), job0))
        return pending

    @classmethod
    def _fused_eligible(cls, job, grid, lengths) -> bool:
        """True when the job routes to a fused Pallas kernel."""
        return cls._fused_demotion_reason(job, grid, lengths) is None

    @classmethod
    def _fused_demotion_reason(cls, job, grid, lengths) -> str | None:
        """None when the job is fused-eligible; otherwise the cap that
        demotes it to the ~6x-slower generic path.

        Jobs whose strategy has a ``_FUSED_STRATEGIES`` entry, with integral
        window grids and a VMEM-sized working set, route to Pallas. Mixed
        history lengths are fine: the kernels take per-ticker real lengths
        (round 3 — a ragged fleet used to silently drop to the generic
        path). A strategy with no fused kernel at all returns a reason too,
        but submit() only LOGS demotions of fused-capable strategies — the
        rest are ordinary routing, not a demotion.
        """
        import numpy as np

        spec = cls._FUSED_STRATEGIES.get(job.strategy)
        if spec is None:
            return f"strategy {job.strategy!r} has no fused kernel"
        if set(grid) != spec.axes:
            return (f"grid axes {sorted(grid)} do not match the fused "
                    f"contract {sorted(spec.axes)}")
        wins = np.concatenate([grid[a] for a in spec.window_axes])
        if wins.size == 0:
            return "empty window grid"   # route to generic, don't crash
        if not np.allclose(wins, np.round(wins)):
            return ("non-integral window values in axes "
                    f"{list(spec.window_axes)}")
        tbl = np.concatenate(
            [grid[a] for a in (spec.table_axes or spec.window_axes)])
        n_tbl = int(np.unique(np.round(tbl)).size)
        if n_tbl > cls._FUSED_MAX_WINDOWS:
            return (f"{n_tbl} distinct table windows exceed the kernel cap "
                    f"of {cls._FUSED_MAX_WINDOWS}")
        if job.strategy in ("donchian", "donchian_hl", "stochastic"):
            # The generic channel paths poison windows beyond their static
            # view bound (MAX_WINDOW) to NaN; the fused kernels have no
            # such bound, so larger windows would silently diverge from the
            # semantics-defining path — keep them generic.
            from ..models import donchian as donchian_mod
            from ..models import stochastic as stoch_mod

            bound = (stoch_mod.MAX_WINDOW if job.strategy == "stochastic"
                     else donchian_mod.MAX_WINDOW)
            if float(wins.max()) > bound:
                return (f"max window {int(wins.max())} exceeds the channel "
                        f"view bound {bound}")
        t_max = int(max(lengths))
        if t_max > cls._FUSED_MAX_BARS:
            return (f"{t_max} bars exceed the kernel VMEM cap of "
                    f"{cls._FUSED_MAX_BARS}")
        return None

    def _mesh_call(self, key, runner, row_arrays, t_real):
        """Run ``runner(*blocks, t_real_block)`` with ticker rows sharded
        over the worker's chip mesh.

        The (ticker x param) sweep is embarrassingly parallel, so the SPMD
        program has no collectives: each chip runs the fused kernel on its
        row block and the metrics stay row-sharded until the stacked result
        copy. Rows pad to a mesh multiple by repeating the last row (the pad
        rows are real compute but land beyond ``len(group)`` in collect, so
        they are never reported). The jit(shard_map) wrapper is cached per
        (strategy, grid, cost) key — rebuilding it per batch would retrace
        every poll.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel import sharding as sharding_mod

        mesh = self._mesh
        axis = mesh.axis_names[0]
        n_pad = sharding_mod.pad_tickers(row_arrays[0].shape[0],
                                         mesh.devices.size)

        row = NamedSharding(mesh, P(axis, None))
        args = [self._jax.device_put(
                    sharding_mod.pad_rows(np.asarray(a, np.float32), n_pad),
                    row)
                for a in row_arrays]
        ragged = t_real is not None
        if ragged:
            args.append(self._jax.device_put(
                sharding_mod.pad_rows(
                    np.asarray(t_real, np.int32).reshape(-1, 1), n_pad),
                row))

        # Every env-resolved kernel substrate must be part of the cache
        # key: the fused runners read DBX_LANES_CAP / DBX_EPILOGUE /
        # DBX_*_TABLE (host-side, via their resolve helpers) while this
        # outer jit(shard_map) traces, so without them an in-process
        # substrate change would silently reuse the stale compiled
        # program on the mesh path — the same cache-key bug class the
        # single-device path fixed by threading each knob as a jit static
        # (dbxlint trace-time-env).
        from ..ops.fused import substrate_defaults

        key = key + (ragged,) + tuple(sorted(substrate_defaults().items()))
        fn = self._mesh_fns.get(key)
        if fn is None:
            from ..ops.metrics import Metrics

            def local(*blks):
                if ragged:
                    *data, tr_blk = blks
                    return runner(*data, tr_blk[:, 0])
                return runner(*blks, None)

            fn = jax.jit(jax.shard_map(
                local, mesh=mesh,
                in_specs=tuple(P(axis, None) for _ in args),
                out_specs=Metrics(*(P(axis, None)
                                    for _ in Metrics._fields)),
                check_vma=False))
            if len(self._mesh_fns) >= self._MESH_FN_CAP:
                # FIFO eviction: a long-lived worker cycling through many
                # distinct grids must not grow compiled executables forever
                # (an evicted entry simply recompiles on next use).
                self._evict_mesh_fn()
            self._mesh_fns[key] = fn
        shape_key = (key, tuple(a.shape for a in args))
        cold = shape_key not in self._seen_shapes
        if cold:
            if len(self._seen_shapes) > 4096:
                self._seen_shapes.clear()
            self._seen_shapes.add(shape_key)
        t_call = time.perf_counter()
        out = fn(*args)
        # Cold dispatch blocks on trace+compile (first call of this
        # program x shape signature); warm is the async launch.
        self._h_jit["cold" if cold else "warm"].observe(
            time.perf_counter() - t_call)
        return out

    _MESH_FN_CAP = 32

    @staticmethod
    def _group_key(job, axes) -> tuple:
        """Cache key capturing everything a mesh runner closes over.

        Hashes the per-parameter AXES (small, as the submit grouping key
        does), not the materialized cartesian product — the product is a
        deterministic function of the axes."""
        return (job.strategy,
                tuple(sorted((k, np.asarray(v).tobytes())
                             for k, v in axes.items())),
                float(job.cost), int(job.periods_per_year or 252))

    def _length_bucket(self, job, grid) -> int:
        """Power-of-two length bucket for the submit grouping key — or 0
        (no bucketing) when the paged path will serve the job: the page
        tables make mixed-length groups first-class (one launch per
        page-count class, pad bounded by one page per ticker), so
        splitting them by length would only multiply launches.

        The collapse is gated on actually being paged-SERVABLE, not just
        paged-capable: the job must carry a digest (page keys memoize per
        digest; a digestless job would drag its whole merged group onto
        the dense fallback) and its GRID must pass the length-independent
        fused gates (axes/integrality/table caps — checked with a 1-bar
        length so only the VMEM bar cap, which the submit-time cap split
        handles, is deferred). Jobs that fail any of this keep the
        power-of-two bucket, so a merged group can only miss the paged
        route through a pool rejection — and that path re-splits by this
        same bucket before stacking densely.

        Strategies in :attr:`_EXACT_LENGTH_STRATEGIES` group by exact
        length instead (the wire block is ``8 + 20 * bars`` bytes)."""
        n_bytes = len(job.ohlcv) or job.panel_bytes_len
        if job.strategy in self._EXACT_LENGTH_STRATEGIES:
            return -n_bytes
        if self._paged_servable(job, grid):
            return 0
        return n_bytes.bit_length()

    def _group_limit(self, group) -> int:
        """Most jobs of ``group`` one launch takes within
        :attr:`_HBM_BUDGET` per chip, from the longest leg's bars (the
        wire block is ``8 + 20 * bars`` bytes) and the grid's combos."""
        n_bytes = max(max(len(j.ohlcv) or j.panel_bytes_len,
                          len(j.ohlcv2) or j.panel_bytes_len2)
                      for j in group)
        t_pad = -(-max((n_bytes - 8) // 20, 1) // 128) * 128
        combos = int(np.prod([len(a.values)
                              for a in group[0].grid.values()]))
        per_job = (t_pad * self._HBM_BYTES_PER_BAR
                   + combos * self._HBM_BYTES_PER_COMBO)
        chips = 1 if self._mesh is None else self._mesh.devices.size
        return max(1, self._HBM_BUDGET // per_job) * chips

    def _paged_servable(self, job, grid) -> bool:
        """THE paged-eligibility predicate — grouping
        (:meth:`_length_bucket`) and :meth:`prefetch` share it, so the
        page warm-up can never drift from what the submit path will
        actually serve paged. Length-independent (the VMEM bar cap is
        the caller's concern: submit splits over-cap groups, prefetch
        gates on ``n_bars`` directly)."""
        return (self.use_paged and job.wf_train == 0
                and not job.best_returns and job.strategy != "pairs"
                and bool(job.panel_digest)
                and job.strategy in self._FUSED_STRATEGIES
                and self._fused_demotion_reason(job, grid, (1,)) is None)

    @staticmethod
    def _topk_request_ok(group) -> bool:
        """Validate a group's ``top_k``/``rank_metric`` request up front.

        An unknown rank metric is validated-bad (complete empty + loud
        error, no compute); walk-forward jobs ignore ``top_k`` entirely —
        their payload is already one stitched OOS row (backtesting.proto
        JobSpec.top_k).
        """
        import logging

        from ..ops.metrics import Metrics

        job0 = group[0]
        if job0.top_k <= 0 or job0.wf_train > 0:
            return True
        metric = job0.rank_metric or "sharpe"
        if metric in Metrics._fields:
            return True
        logging.getLogger("dbx.compute").error(
            "jobs %s request top-k by unknown metric %r (known: %s); "
            "completing with empty metrics", [j.id for j in group], metric,
            ", ".join(Metrics._fields))
        return False

    def prefetch(self, jobs) -> int:
        """Control-thread batch warm-up (the worker's ``DBX_PREFETCH``
        leg, round 14): decode payload bytes into the host panel cache
        and pre-stage paged groups' device pages while the compute
        pipeline runs earlier batches.

        Strictly an overlap optimization — every warmed path re-resolves
        through the same caches on the compute thread, so a skipped or
        failed prefetch costs nothing but the overlap. Append jobs are
        left alone (their delta-splice path must not materialize the
        full panel early) and a zero-budget cache
        (``DBX_PANEL_CACHE_MB=0``) skips the decode it could not retain.
        Returns the number of panels decoded (the worker's prefetch span
        is emitted only when real work happened).
        """
        cache = self.panel_cache
        if cache.max_bytes <= 0:
            return 0
        warmed = 0
        decoded: dict = {}
        paged_groups: dict[str, tuple[list, list]] = {}
        for job in jobs:
            if job.append_parent_digest:
                continue
            for digest, raw in ((job.panel_digest, job.ohlcv),
                                (job.panel_digest2, job.ohlcv2)):
                if (not digest or not raw or digest in decoded
                        or cache.contains_series(digest)):
                    continue
                try:
                    s = data_mod.from_wire_bytes(raw)
                except Exception:
                    log.exception(
                        "prefetch decode failed for digest %s; the "
                        "compute thread will decode (and error) inline",
                        digest[:16])
                    continue
                cache.put_series(digest, s)
                decoded[digest] = s
                warmed += 1
            # Page-pool warm-up: upload the pool-missing pages of paged-
            # servable jobs now, so the submit-side prepare finds them
            # resident (pages_new == 0 -> the h2d-skip fast path). Only
            # panels decoded in THIS call join — a digest-only job whose
            # panel is already host-cached had its pages prepared when
            # that panel first crossed the paged submit path. Gated on
            # the SHARED servability predicate: warming pages the submit
            # path will demote to dense would waste H2D and evict pages
            # live groups are about to gather.
            s = decoded.get(job.panel_digest)
            if (s is not None and s.n_bars <= self._FUSED_MAX_BARS
                    and self._fused_ops.paged_supported(job.strategy)):
                try:
                    grid = wire.grid_from_proto(job.grid)
                except Exception:
                    continue
                if not self._paged_servable(job, grid):
                    continue
                digests, series = paged_groups.setdefault(job.strategy,
                                                          ([], []))
                if job.panel_digest not in digests:
                    digests.append(job.panel_digest)
                    series.append(s)
        for strategy, (digests, series) in paged_groups.items():
            try:
                # A pool rejection (None) is fine — the submit path will
                # take the dense fallback exactly as without prefetch.
                self.panel_cache.pages.prepare(
                    digests, series, self._fused_ops.paged_fields(strategy))
            except Exception:
                log.exception("page-pool prefetch failed for %s; submit "
                              "will prepare inline", strategy)
        return warmed

    def _resolve_series(self, job, *, leg2: bool = False):
        """One leg's decoded panel: host cache -> inline bytes ->
        FetchPayload (the second chance for a panel evicted between the
        control thread's pre-dispatch probe and this decode). Returns
        ``(series, cache_hit)``. An unresolvable digest raises — the
        worker loop logs it and leaves the lease to requeue the batch
        (by then the dispatcher has forgotten the delivery, so the
        re-dispatch ships full bytes): miss -> fetch -> full job, never a
        failed job."""
        digest = job.panel_digest2 if leg2 else job.panel_digest
        raw = job.ohlcv2 if leg2 else job.ohlcv
        if digest:
            s = self.panel_cache.get_series(digest)
            if s is not None:
                return s, True
        if not raw and digest and self.payload_fetcher is not None:
            # The recovery RPC gets its OWN span: it can run inside the
            # decode window (compute-thread race leg), and a 30s network
            # stall must read as transport in timeline attribution, not
            # as decode work (obs.timeline maps worker.payload_fetch ->
            # transport, innermost-wins over the enclosing decode span).
            t0_wall, t0 = time.time(), time.perf_counter()
            raw = self.payload_fetcher(digest)
            obs.emit_span("worker.payload_fetch", t0_wall,
                          time.perf_counter() - t0,
                          pairs=obs.job_trace_pairs([job]),
                          digest=digest, ok=bool(raw))
        if not raw:
            raise ValueError(
                f"job {job.id}: digest-only payload "
                f"{digest[:16] if digest else '?'} is in no cache and not "
                "fetchable; leaving the lease to requeue it")
        s = data_mod.from_wire_bytes(raw)
        if digest:
            self.panel_cache.put_series(digest, s)
        return s, False

    def _resolve_append_series(self, job):
        """Extended panel for an append job: digest cache -> splice (the
        cached BASE panel + ``JobSpec.append_delta`` — the delta-only
        dispatch fast path, no full panel on the wire) -> inline bytes ->
        FetchPayload. Returns ``(series, cache_hit)``."""
        digest = job.panel_digest
        if (digest and not job.ohlcv and job.append_delta
                and job.append_parent_digest
                and not self.panel_cache.contains_series(digest)):
            base = self.panel_cache.get_series(job.append_parent_digest)
            if base is not None and base.n_bars == int(job.append_base_len):
                delta = data_mod.from_wire_bytes(job.append_delta)
                s = data_mod.OHLCV(*(
                    np.concatenate([np.asarray(b), np.asarray(d)])
                    for b, d in zip(base, delta)))
                self.panel_cache.put_series(digest, s)
                return s, True
        return self._resolve_series(job)

    def _submit_append_job(self, job):
        """One streaming append job: advance the base panel's carry
        checkpoint by the appended slice (O(ΔT)); a missing/stale
        checkpoint falls back to a full scan-form rebuild over the
        extended panel (degraded, never a failed job). Either way the
        NEW checkpoint is stored under the extended panel's digest, so
        the next append in the chain hits."""
        from ..parallel import sweep as sweep_mod
        from ..streaming import recurrent

        t0 = time.perf_counter()
        t0_wall = time.time()
        trace_pairs = obs.job_trace_pairs([job])
        if (not recurrent.supports_strategy(job.strategy)
                or job.strategy == "pairs"):
            # Validated-bad, the malformed-pairs discipline: the AppendBars
            # wire carries ONE panel, so two-legged strategies (and any
            # family without a streaming spec) complete loudly empty
            # instead of requeue-looping through leases.
            log.error("append job %s: strategy %r is not streamable over "
                      "AppendBars; completing with empty metrics", job.id,
                      job.strategy)
            return ([job], None, t0, 0, None)
        axes = wire.grid_from_proto(job.grid)
        grid = {k: np.asarray(v)
                for k, v in sweep_mod.product_grid(**axes).items()}
        cost = float(job.cost)
        ppy = int(job.periods_per_year or 252)
        skey = recurrent.stream_key(job.strategy, grid, cost, ppy)
        series, _ = self._resolve_append_series(job)
        fields = {
            f: np.asarray(getattr(series, f), np.float32)[None, :]
            for f in recurrent.stream_fields(job.strategy)}
        base_len = int(job.append_base_len)
        hit = False
        try:
            carry = (self.carry_store.get((job.panel_digest, skey))
                     if job.panel_digest else None)
            if carry is not None and carry.n_bars == series.n_bars:
                # Retried delivery of an already-advanced append: serve
                # the stored checkpoint, don't advance twice.
                hit = True
            else:
                carry = None
                if 0 < base_len < series.n_bars:
                    base_carry = self.carry_store.get(
                        (job.append_parent_digest, skey))
                    if (base_carry is not None
                            and base_carry.n_bars == base_len):
                        carry = recurrent.append_step(
                            base_carry,
                            {f: v[:, base_len:]
                             for f, v in fields.items()})
                        hit = True
                if carry is None:
                    carry = recurrent.build_carry(
                        job.strategy, fields, grid, cost=cost,
                        periods_per_year=ppy)
        except (ValueError, KeyError) as e:
            # Validated-bad (a grid the family cannot price, an empty
            # axis, ...): complete loudly empty — requeue-looping through
            # leases would never fix a malformed spec.
            log.error("append job %s: %s; completing with empty metrics",
                      job.id, e)
            return ([job], None, t0, 0, None)
        if job.panel_digest:
            self.carry_store.put((job.panel_digest, skey), carry)
        m = recurrent.finalize(carry)
        self._c_append["carry_hit" if hit else "full_reprice"].inc()
        # The append span carries the hit flag: obs.timeline charges hit
        # windows to the `carry_hit` pseudo-stage (the streaming twin of
        # panel_cache_hit), full reprices stay execute.
        obs.emit_span("worker.append", t0_wall,
                      time.perf_counter() - t0, pairs=trace_pairs,
                      job=job.id, carry_hit=hit, bars=series.n_bars,
                      delta_bars=series.n_bars - base_len)
        # Histogram only (no group=): an execute envelope span over the
        # SAME interval would tie worker.append at equal priority in
        # timeline attribution, and the tie-break (later t0) is clock
        # jitter — a served O(ΔT) append must never read as phantom
        # execute work.
        self._observe_submit(job.strategy, "append", t0)
        # donate=False: finalize's outputs may alias buffers the stored
        # carry still owns — donating them would invalidate the
        # checkpoint the next append in the chain advances.
        return ([job], _start_result_copy(m, donate=False), t0, 1, None)

    def _submit_scenario_group(self, job):
        """One coalesced scenario spec-batch job (JobSpec.scenario_batch):
        a single launch regenerates each of the K scenario panels
        IN-TRACE inside the fused sweep — the synthetic panels never
        exist in HBM (``lax.map`` over specs holds one scenario's working
        set at a time, so device bytes are O(1) in K; only the BASE panel
        rides the payload/digest legs). Every spec completes under its
        own queued job id, so queue semantics are per-scenario exactly as
        if the K jobs had dispatched materialized.

        Degradation: any fused-leg failure (unsupported family drifting
        past the dispatcher gate, kill switch flipped mid-lease, a trace
        error) drops to :meth:`_submit_scenario_materialized` — the
        in-process twin of the dispatcher's materialized path — and
        fires a flight-recorder anomaly so the demotion is capturable.
        An unresolvable BASE raises, leaving the lease to requeue (by
        then the dispatcher re-ships bytes): degraded, never a failed
        job."""
        from ..parallel import sweep as sweep_mod
        from ..scenarios import synth

        t0 = time.perf_counter()
        specs = list(job.scenario_batch)
        # Base resolution rides the ordinary digest machinery: host
        # cache -> inline bytes -> FetchPayload; raises on miss.
        series, _ = self._resolve_series(job)
        pseudo = [_ScenarioJob(s.id, job.grid, s.trace_id,
                               job.parent_span_id) for s in specs]
        try:
            if not self._fused_ops.scenario_fused_enabled():
                raise ValueError("DBX_SCENARIO_FUSED=0")
            base = {f: np.asarray(getattr(series, f), np.float32)
                    for f in ("open", "high", "low", "close", "volume")}
            n_bars = int(specs[0].n_bars) or series.n_bars
            block = max(int(specs[0].block), 1)
            regimes = max(int(specs[0].regimes), 1)
            axes = wire.grid_from_proto(job.grid)
            grid = {k: np.asarray(v, np.float32) for k, v
                    in sweep_mod.product_grid(**axes).items()}
            # ScenarioSpec.seed carries the EFFECTIVE 64-bit seed for
            # batch members (dispatcher-derived from host-precision
            # params) — the worker only splits it into the int31 words
            # the generator's key derivation folds.
            words = [synth.seed_words(int(s.seed)) for s in specs]
            m = self._fused_ops.fused_scenario_sweep(
                job.strategy, base,
                np.asarray([w[0] for w in words], np.int32),
                np.asarray([w[1] for w in words], np.int32),
                np.asarray([s.vol_scale for s in specs], np.float32),
                np.asarray([s.shock for s in specs], np.float32),
                grid, n_bars=n_bars, block=block, regimes=regimes,
                cost=float(job.cost),
                periods_per_year=int(job.periods_per_year or 252))
        except Exception as e:     # noqa: BLE001 — demote, never fail
            # The jobs still complete, but a fused-leg failure on the
            # chip is a device fault, not a route: it logs as an error
            # and fires a named flight trigger, which chip_smoke.py
            # fails on.
            log.error(
                "scenario batch %s (%s, %d specs): fused leg failed "
                "(%s); falling back to in-process materialization",
                job.id, job.strategy, len(specs), e)
            obs_flight.trigger(
                "scenario_fused_fail", subject=job.id,
                strategy=job.strategy, specs=len(specs), error=str(e))
            return self._submit_scenario_materialized(job, specs,
                                                      series, t0)
        self._c_scenario_route["fused"].inc(len(specs))
        P = sweep_mod.grid_size(grid) if grid else 1
        self._observe_submit(
            job.strategy, "scenario", t0,
            cold_key=("scenario", job.strategy, n_bars, block, regimes,
                      P, len(specs)),
            group=pseudo, bars=n_bars, combos=len(specs) * P)
        return [(pseudo, _start_result_copy(m), t0, len(specs), None)]

    def _submit_scenario_materialized(self, job, specs, series, t0):
        """The worker-side materialized rung of the scenario degradation
        ladder: host-generate each spec's panel (the same
        ``synth.generate`` program the dispatcher's materialized path
        runs, under the same effective seed — bit-identical bytes) and
        resubmit the batch as K ordinary inline-payload jobs through the
        normal routing. A spec whose generation raises is validated-bad
        and completes loudly empty (a malformed spec requeue-loops
        forever; the dispatcher's own materialized path would have
        failed it the same way)."""
        from ..scenarios import synth

        self._c_scenario_route["materialized"].inc(len(specs))
        expanded, bad = [], []
        for s in specs:
            # generate() reads only the shape/scale fields off params —
            # the effective seed arrives pre-derived in s.seed, so the
            # sequence-number field is irrelevant here.
            params = synth.ScenarioParams(
                n_bars=int(s.n_bars), block=int(s.block),
                regimes=int(s.regimes), vol_scale=float(s.vol_scale),
                shock=float(s.shock))
            try:
                panel = synth.generate(series, params, int(s.seed))
            except (ValueError, ZeroDivisionError) as e:
                log.error("scenario job %s: generation failed (%s); "
                          "completing with empty metrics", s.id, e)
                bad.append(s)
                continue
            spec = pb.JobSpec()
            spec.CopyFrom(job)
            del spec.scenario_batch[:]
            spec.ClearField("scenario")
            spec.id = s.id
            spec.trace_id = s.trace_id
            spec.ohlcv = data_mod.to_wire_bytes(panel)
            spec.panel_digest = ""
            spec.panel_bytes_len = 0
            expanded.append(spec)
        pending = []
        if bad:
            pending.append(
                ([_ScenarioJob(s.id, job.grid, s.trace_id,
                               job.parent_span_id) for s in bad],
                 None, t0, 0, None))
        if expanded:
            pending.extend(self.submit(expanded))
        return pending

    def _decode_group(self, group):
        """Cache-aware group decode (leg 1 — the pairs path drives
        :meth:`_resolve_series` per leg itself) under the traced
        ``worker.decode`` span. The span's ``cache_hit`` attr is True
        when EVERY panel came from the digest cache (decode skipped) —
        obs.timeline charges such windows to the ``panel_cache_hit``
        pseudo-stage instead of mis-reading a span-less gap as
        transport."""
        pairs = obs.job_trace_pairs(group)
        t0_wall = time.time()
        t_dec = time.perf_counter()
        series = []
        hits = 0
        for j in group:
            s, hit = self._resolve_series(j)
            series.append(s)
            hits += 1 if hit else 0
        dur = time.perf_counter() - t_dec
        self._h_decode.observe(dur)
        self._c_decode_bytes.inc(sum(len(j.ohlcv) for j in group))
        obs.emit_span("worker.decode", t0_wall, dur, pairs=pairs,
                      jobs=len(group), cache_hit=hits == len(group),
                      cache_hits=hits)
        return series, hits

    def _uniform_field_arrays(self, group, series, fields):
        """Per-field ``(n, T)`` arrays for a uniform-length group, plus an
        ``h2d_cache_hit`` flag. With content digests on every job and no
        mesh, each panel is cached on DEVICE as its ``(5, T)`` field block
        keyed by digest: a hit builds the group stack device-side — no
        host->device copy at all; a miss uploads once and primes the
        cache. Digestless jobs (hand-built specs, pre-dedupe dispatchers)
        and mesh workers (whose arrays must device_put with an explicit
        sharding) keep the host ``np.stack`` path."""
        digests = [j.panel_digest for j in group]
        if self._mesh is not None or not all(digests):
            return [np.stack([np.asarray(getattr(s, f)) for s in series])
                    for f in fields], False
        import jax.numpy as jnp

        rows, all_hit = [], True
        for d, s in zip(digests, series):
            blk = self.panel_cache.get_device(d)
            if blk is None:
                all_hit = False
                host = np.stack([np.asarray(f, np.float32) for f in s])
                blk = self._jax.device_put(host)
                self.panel_cache.put_device(d, blk, host.nbytes)
            rows.append(blk)
        idx = [data_mod.OHLCV._fields.index(f) for f in fields]
        return [jnp.stack([r[i] for r in rows]) for i in idx], all_hit

    def _finish_group(self, jobs, m, t0, n_real, job0, *,
                      h2d_hit: bool = False):
        """Shared tail of every sweep submit path: optional on-device top-k
        reduction, then the stacked async result copy. ``h2d_hit`` rides
        the pending entry so collect's d2h span can report that the
        submit-side panel upload was served from the device cache."""
        topk = None
        if job0.top_k > 0 and job0.wf_train == 0:
            metric = job0.rank_metric or "sharpe"
            # Grid size, not m.shape: reading a device array's shape is
            # free, but np.asarray would sync the pipeline here.
            P = wire.grid_n_combos(job0.grid)
            idx, m = _topk_reduce(m, metric, min(int(job0.top_k), P))
            topk = (idx, metric)
        return (jobs, _start_result_copy(m), t0, n_real, topk, h2d_hit)

    def submit(self, jobs) -> list:
        """Dispatch a batch: decode, transfer, launch kernels, start the
        device->host result copy — all without blocking on the device.

        Returns an opaque handle for :meth:`collect`. The 9 metric fields
        are stacked into ONE device array and fetched with a single async
        transfer: nine per-field ``np.asarray`` round-trips measured ~1.9 s
        per 100-job group vs ~1.3 s for the stacked copy (before PR 1, on a
        chip reached over the network), and ``copy_to_host_async`` lets
        the next batch's decode/H2D/compute proceed while this one's results stream back.
        """
        import jax.numpy as jnp

        from ..models import base as models_base
        from ..parallel import sweep as sweep_mod

        jobs = list(jobs)
        # Streaming append jobs peel off first: each advances (or
        # rebuilds) its own carry checkpoint — O(ΔT) work per job, no
        # batching needed or wanted (the carry is per-panel state).
        stream_pending = [self._submit_append_job(j) for j in jobs
                          if j.append_parent_digest]
        jobs = [j for j in jobs if not j.append_parent_digest]
        # Scenario spec batches peel next: each carrier JobSpec is ONE
        # fused generator x sweep launch that completes its K coalesced
        # scenario records individually (megakernel route).
        for j in [j for j in jobs if j.scenario_batch]:
            stream_pending.extend(self._submit_scenario_group(j))
        jobs = [j for j in jobs if not j.scenario_batch]
        # Route accounting for the materialized rung: scenario jobs that
        # arrive as ordinary concrete panels (old worker capability,
        # DBX_SCENARIO_FUSED=0, digestless base, unsupported family).
        n_mat = sum(1 for j in jobs if j.scenario.base_digest)
        if n_mat:
            self._c_scenario_route["materialized"].inc(n_mat)
        # Group stackable jobs: same strategy, grid, cost (and walk-forward
        # windowing). Mixed history lengths stack fine — both the fused
        # kernels (per-ticker t_real) and the generic path (pad_and_stack +
        # bar_mask) handle ragged groups — but lengths are bucketed by
        # power of two (on the wire byte length, which is linear in bars)
        # so co-batching never pads a job more than ~2x, and one oversized
        # job cannot push a whole group over the fused VMEM cap onto the
        # generic path.
        groups: dict[tuple, list[pb.JobSpec]] = {}
        for job in jobs:
            grid = wire.grid_from_proto(job.grid)
            n2 = len(job.ohlcv2) or job.panel_bytes_len2   # 0: single-asset
            key = (job.strategy,
                   tuple(sorted((k, v.tobytes()) for k, v in grid.items())),
                   # Digest-only dispatches ship no bytes; the stamped
                   # panel_bytes_len keeps them in the same length bucket
                   # as their full-payload twins. With the paged path
                   # live the bucket collapses to 0 — mixed lengths fuse.
                   self._length_bucket(job, grid),
                   -n2 if job.strategy in self._EXACT_LENGTH_STRATEGIES
                   else n2.bit_length(),
                   job.cost, job.periods_per_year,
                   job.wf_train, job.wf_test, job.wf_metric,
                   job.top_k, job.rank_metric, job.best_returns)
            groups.setdefault(key, []).append(job)

        pending = stream_pending
        # A long-history group the size of a worker's lease would
        # overflow device memory on a route the router admits: launch it
        # in parts of at most _group_limit jobs.
        parts = [g[i:i + n] for g in groups.values()
                 for n in [self._group_limit(g)]
                 for i in range(0, len(g), n)]
        for group in parts:
            t0 = time.perf_counter()
            if not self._topk_request_ok(group):
                # Validated-bad, like a malformed pairs leg: complete with
                # empty blocks instead of requeue-looping through leases.
                pending.append((list(group), None, t0, 0, None))
                continue
            if group[0].best_returns and (group[0].strategy == "pairs"
                                          or group[0].wf_train > 0):
                # Validated-bad, like a bad top-k request: the DBXP contract
                # is single-asset full-history sweeps (the dispatcher CLI
                # enforces this; a hand-built spec gets a loud empty).
                log.error(
                    "jobs %s: best_returns is not supported for %s jobs; "
                    "completing empty", [j.id for j in group],
                    "pairs" if group[0].strategy == "pairs"
                    else "walk-forward")
                pending.append((list(group), None, t0, 0, None))
                continue
            if group[0].strategy == "pairs":
                pending.append(self._submit_pairs_group(group, t0))
                self._observe_submit(
                    "pairs", "pairs_wf" if group[0].wf_train > 0
                    else "pairs", t0, group=group)
                continue
            # The decode span adopts the GROUP's traces (a batch can hold
            # several groups; the batch-level context set by the worker
            # loop would attribute one group's decode to every job); a
            # digest-cache hit skips the decode and the span says so
            # (`cache_hit` attr).
            series, _ = self._decode_group(group)
            lengths = [s.n_bars for s in series]
            if group[0].wf_train > 0:
                pending.append(self._submit_walkforward_group(
                    group, series, lengths, t0))
                self._observe_submit(group[0].strategy, "walkforward", t0,
                                     group=group)
                continue
            if group[0].best_returns:
                pending.append(self._submit_best_returns_group(
                    group, series, lengths, t0))
                self._observe_submit(group[0].strategy, "best_returns", t0,
                                     group=group)
                continue
            # JobSpec.grid carries per-parameter AXES; the cartesian product
            # is materialized worker-side (backtesting.proto JobSpec.grid).
            axes = wire.grid_from_proto(group[0].grid)
            grid = sweep_mod.product_grid(**axes)
            strategy = models_base.get_strategy(group[0].strategy)
            ppy = group[0].periods_per_year or 252
            demotion = (self._fused_demotion_reason(group[0], axes, lengths)
                        if self.use_fused else None)
            fused_ok = self.use_fused and demotion is None
            t_max_g = int(max(lengths))
            if (not fused_ok and self._mesh is not None
                    and t_max_g > self._FUSED_MAX_BARS
                    and len(group) < self._mesh.devices.size):
                # Long-context route: a history too long for the fused
                # VMEM cap, on a meshed worker whose ticker axis cannot
                # fill the chips, shards its BAR axis instead of demoting
                # to one device's generic path.
                ts_reason = self._timeshard_reason(group[0], axes, lengths)
                if ts_reason is None:
                    log.info(
                        "jobs %s (%s) routed to the time-sharded "
                        "long-context path (%d bars over %d chips)",
                        [j.id for j in group], group[0].strategy, t_max_g,
                        self._mesh.devices.size)
                    pending.extend(self._submit_timeshard_groups(
                        group, series, lengths, t0, axes))
                    self._observe_submit(
                        group[0].strategy, "timeshard", t0,
                        cold_key=("timeshard", len(group), t_max_g)
                        + self._group_key(group[0], axes), group=group,
                        bars=t_max_g, combos=sweep_mod.grid_size(grid)
                        if grid else 1)
                    continue
                # The group-level gate uses min(lengths) for the halo
                # bound, so ONE short job in a ragged group would drag
                # every genuinely long job off the route. Re-gate per
                # job: the submit path already shards per length
                # subgroup, so a partial route is natural. The per-job
                # gate keeps the LONG-CONTEXT condition too — a short
                # job that merely shares the group must stay on the
                # (faster) single-device/fused path, not be dragged onto
                # distributed cumsums for a panel that fits one chip.
                ok_idx = [i for i, t in enumerate(lengths)
                          if int(t) > self._FUSED_MAX_BARS
                          and timeshard_route_reason(
                              group[0].strategy, axes, [int(t)],
                              self._mesh.devices.size) is None]
                if ok_idx:
                    log.info(
                        "jobs %s (%s) route time-sharded individually; "
                        "%s stay generic (%s)",
                        [group[i].id for i in ok_idx], group[0].strategy,
                        [group[i].id for i in range(len(group))
                         if i not in set(ok_idx)], ts_reason)
                    pending.extend(self._submit_timeshard_groups(
                        [group[i] for i in ok_idx],
                        [series[i] for i in ok_idx],
                        [int(lengths[i]) for i in ok_idx], t0, axes))
                    self._observe_submit(
                        group[0].strategy, "timeshard", t0,
                        cold_key=("timeshard", len(ok_idx),
                                  max(int(lengths[i]) for i in ok_idx))
                        + self._group_key(group[0], axes),
                        group=[group[i] for i in ok_idx],
                        bars=max(int(lengths[i]) for i in ok_idx),
                        combos=sweep_mod.grid_size(grid) if grid else 1)
                    rest = [i for i in range(len(group))
                            if i not in set(ok_idx)]
                    if not rest:
                        continue
                    # The remainder restarts the clock: its route
                    # observation (and completion elapsed) must not
                    # re-attribute the timeshard subset's submit wall.
                    t0 = time.perf_counter()
                    group = [group[i] for i in rest]
                    series = [series[i] for i in rest]
                    lengths = [int(lengths[i]) for i in rest]
                    # The remainder is a different (shorter) panel:
                    # re-evaluate the fused gate for it.
                    demotion = (self._fused_demotion_reason(
                        group[0], axes, lengths) if self.use_fused
                        else None)
                    fused_ok = self.use_fused and demotion is None
                    t_max_g = int(max(lengths))
                else:
                    log.warning(
                        "jobs %s (%s) are long-context (%d bars) but not "
                        "time-shardable (%s); falling through to the "
                        "generic path", [j.id for j in group],
                        group[0].strategy, t_max_g, ts_reason)
            if fused_ok:
                pending.extend(self._submit_fused_group(
                    group, series, lengths, axes, grid, t0))
                continue
            if (self.use_paged and demotion is not None
                    and group[0].strategy in self._FUSED_STRATEGIES
                    and t_max_g > self._FUSED_MAX_BARS):
                # Over-cap ragged groups route through paging FIRST: the
                # paged group key no longer buckets by length, so one
                # oversized panel would otherwise demote every under-cap
                # member of its merged group to the generic path. Split:
                # the under-cap subset keeps the fused (paged) route,
                # only the genuinely-long remainder stays demoted.
                ok_idx = [i for i, t in enumerate(lengths)
                          if int(t) <= self._FUSED_MAX_BARS]
                if ok_idx and len(ok_idx) < len(group) \
                        and self._fused_demotion_reason(
                            group[0], axes,
                            [int(lengths[i]) for i in ok_idx]) is None:
                    log.info(
                        "jobs %s (%s) route paged-fused under the VMEM "
                        "bar cap; %s stay demoted (%s)",
                        [group[i].id for i in ok_idx], group[0].strategy,
                        [group[i].id for i in range(len(group))
                         if i not in set(ok_idx)], demotion)
                    pending.extend(self._submit_fused_group(
                        [group[i] for i in ok_idx],
                        [series[i] for i in ok_idx],
                        [int(lengths[i]) for i in ok_idx], axes, grid, t0))
                    rest = [i for i in range(len(group))
                            if i not in set(ok_idx)]
                    # The remainder restarts the clock (the timeshard
                    # split's discipline): its route observation must not
                    # re-attribute the fused subset's submit wall.
                    t0 = time.perf_counter()
                    group = [group[i] for i in rest]
                    series = [series[i] for i in rest]
                    lengths = [int(lengths[i]) for i in rest]
                    t_max_g = int(max(lengths))
            if (demotion is not None
                    and group[0].strategy in self._FUSED_STRATEGIES):
                # A fleet silently dropping to the ~6x-slower generic
                # path is a throughput bug nobody can see; name the cap.
                log.warning(
                    "jobs %s (%s) demoted to the generic path: %s",
                    [j.id for j in group], group[0].strategy, demotion)
            if len(set(int(t) for t in lengths)) > 1:
                # The generic stack pads every series to the group max —
                # the padding-waste counter must see this path too.
                self._c_pad_bars["dense"].inc(
                    int(sum(t_max_g - int(t) for t in lengths)))
            batch, _, mask = data_mod.pad_and_stack(series)
            # One chunk-eligibility rule for both branches: the mesh and
            # single-device backends must agree on memory bounding.
            P = sweep_mod.grid_size(grid) if grid else 1
            chunk = (self.param_chunk
                     if self.param_chunk and P % self.param_chunk == 0
                     else None)
            if self._mesh is not None:
                # The generic path's multi-chip story already exists in
                # the library: device_put_sweep + sharded_sweep (tickers
                # over the mesh, grid replicated). The two memory valves
                # compose: the mesh divides the ticker axis, param_chunk
                # still bounds the param axis's live set per chip.
                from ..parallel import sharding as sharding_mod

                sh_panel, sh_grid, sh_mask, _ = (
                    sharding_mod.device_put_sweep(
                        self._mesh, batch,
                        {k: jnp.asarray(v) for k, v in grid.items()},
                        bar_mask=mask))
                m = sharding_mod.sharded_sweep(
                    self._mesh, sh_panel, strategy, sh_grid,
                    cost=group[0].cost, bar_mask=sh_mask,
                    periods_per_year=ppy, param_chunk=chunk)
            else:
                panel = type(batch)(*(jnp.asarray(f) for f in batch))
                kwargs = dict(cost=group[0].cost,
                              bar_mask=jnp.asarray(mask),
                              periods_per_year=ppy)
                if chunk:
                    m = sweep_mod.chunked_sweep(
                        panel, strategy, grid, param_chunk=chunk,
                        **kwargs)
                else:
                    m = sweep_mod.jit_sweep(panel, strategy, grid,
                                            **kwargs)
            route = ("generic"
                     + ("_mesh" if self._mesh is not None else ""))
            # Shape in the cold key: jit compiles per (rows, bars), so a
            # new group size IS a compile, not an execute.
            self._observe_submit(
                group[0].strategy, route, t0,
                cold_key=(route, len(group), t_max_g)
                + self._group_key(group[0], axes), group=group,
                bars=t_max_g, combos=P)
            pending.append(self._finish_group(group, m, t0, len(group),
                                              group[0]))
        return pending

    def _try_paged_submit(self, group, series, lengths, grid):
        """Paged fused submit: resolve the group against the device page
        pool (uploading only pool-missing pages) and sweep it through
        the page tables — one launch per page-count class, mixed lengths
        welcome. Returns ``(metrics, pool_warm)`` where ``pool_warm``
        means every page was already device-resident (no upload — the
        paged analogue of the device-block h2d cache hit), or None when
        the pool rejects the group (working set over the pool bound) —
        the caller falls back to the dense stacks, degraded never
        failed. Fields come from the paged registry itself
        (`fused.paged_fields`) so the tables can never be prepared for a
        different column set than `fused_paged_sweep` validates
        against."""
        prep = self.panel_cache.pages.prepare(
            [j.panel_digest for j in group], series,
            self._fused_ops.paged_fields(group[0].strategy))
        if prep is None:
            return None
        pool_arr, tables, info = prep
        if info["pad_bars_new"]:
            self._c_pad_bars["paged"].inc(info["pad_bars_new"])
        job0 = group[0]
        m = self._fused_ops.fused_paged_sweep(
            job0.strategy, pool_arr, tables,
            np.asarray(lengths, np.int32), grid,
            cost=float(job0.cost),
            periods_per_year=int(job0.periods_per_year or 252))
        return m, info["pages_new"] == 0

    def _tuned_schedule_for(self, job0, lengths, grid) -> dict | None:
        """Registry consultation at group-submit time (tune/ round 11):
        the tuned substrate schedule for this group's (family,
        shape-bucket, platform) — running a first-contact autotune under
        ``DBX_AUTOTUNE`` — or None (hardcoded defaults). NEVER raises:
        a broken registry or failed tune degrades to today's routing."""
        try:
            n_bars = int(max(lengths))
            n_combos = max((int(np.asarray(v).shape[0])
                            for v in grid.values()), default=1)
            bucket = self._tune.shape_bucket(n_bars, n_combos)
            family = job0.strategy
            sched = self.schedule_registry.lookup(family, bucket,
                                                  self._platform)
            mode = self._tune.autotune_mode()
            if (sched is None and mode != "off"
                    and (family, bucket) not in self._tuned_attempted):
                self._tuned_attempted.add((family, bucket))
                # page_bars joins the search space only under the model
                # prior: it binds at pool construction, so a live
                # measurement through the dense wrapper could not tell
                # the candidates apart anyway.
                sched = self._autotuner.tune(
                    family, bucket, self._platform, n_bars=n_bars,
                    n_combos=n_combos,
                    measure=(None if mode == "model"
                             else self._autotune_measure(job0, grid)),
                    paged=(mode == "model" and self.use_paged
                           and self._fused_ops.paged_supported(family)))
            if sched:
                self._publish_tuned_info(family, bucket, sched)
            return sched
        except Exception:
            log.exception("tuned-schedule consultation failed; serving "
                          "hardcoded substrate defaults")
            return None

    def _autotune_measure(self, job0, grid):
        """The live measurement harness handed to the autotuner: one
        representative single-ticker sweep of this group's family/grid
        under the candidate substrate tuple (warm run timed — compile
        excluded, it is what the fleet compile cache amortizes)."""
        spec = self._FUSED_STRATEGIES[job0.strategy]
        series, _hit = self._resolve_series(job0)
        arrays = [np.asarray(getattr(series, f), np.float32)[None, :]
                  for f in spec.fields]
        cost, ppy = job0.cost, job0.periods_per_year or 252
        jax = self._jax

        def measure(substrates: dict) -> float:
            with self._fused_ops.tuned_schedule(substrates):
                jax.block_until_ready(
                    spec.run(*arrays, grid, cost, ppy, None))
                t0 = time.perf_counter()
                jax.block_until_ready(
                    spec.run(*arrays, grid, cost, ppy, None))
                return time.perf_counter() - t0
        return measure

    def _publish_tuned_info(self, family: str, bucket: str,
                            sched: dict) -> None:
        """``dbx_tuned_substrate_info`` — the tuned twin of
        ``dbx_fused_substrate_info``: constant 1, labels carry which
        tuned substrates route this (family, shape-bucket). Fixed label
        keys ("default" = knob left on hardcoded routing); family and
        bucket are bounded (strategy registry x clamped pow2 rails)."""
        key = (family, bucket, tuple(sorted(sched.items())))
        if key in self._tuned_info_seen:
            return
        self._tuned_info_seen.add(key)
        table = next((v for k, v in sorted(sched.items())
                      if k.startswith("table_")), "default")
        self._obs.gauge(
            "dbx_tuned_substrate_info",
            help="constant 1; labels carry the tuned substrate schedule "
                 "serving this (kernel family, shape-bucket) — the "
                 "tuned-vs-default twin of dbx_fused_substrate_info",
            kernel=family, bucket=bucket,
            epilogue=sched.get("epilogue", "default"),
            table=table,
            lanes_cap=sched.get("lanes_cap", "default"),
            page_bars=sched.get("page_bars", "default")).set(1)

    def _submit_fused_group(self, group, series, lengths, axes, grid, t0,
                            *, allow_paged: bool = True):
        """Tuned-schedule activation around one fused group submit: the
        registry's winner for this (family, shape-bucket) routes every
        substrate resolver the wrappers call inside — below explicit
        args and env knobs, above hardcoded defaults — and folds into
        the jit cache keys exactly like an env knob flip (the wrappers'
        static args and the mesh path's substrate_defaults() key both
        resolve through the same chain)."""
        sched = self._tuned_schedule_for(group[0], lengths, grid)
        if not sched:
            return self._submit_fused_group_routed(
                group, series, lengths, axes, grid, t0,
                allow_paged=allow_paged)
        with self._fused_ops.tuned_schedule(sched):
            return self._submit_fused_group_routed(
                group, series, lengths, axes, grid, t0,
                allow_paged=allow_paged)

    def _submit_fused_group_routed(self, group, series, lengths, axes,
                                   grid, t0, *, allow_paged: bool = True):
        """Fused submit of one (possibly mixed-length) group.

        Paged route first (digest-keyed device pages + page tables —
        round 10); dense stacks as the fallback for digestless jobs,
        mesh workers, pool rejections and ``DBX_PAGED=0``. Repeat-last
        padding + per-ticker lengths either way: pad bars earn zero
        return and hold the final position, and all metric reductions
        use each ticker's real length. Only the columns the kernel
        consumes (spec.fields) reach the device. Returns a LIST of
        pending entries for :meth:`collect` — one normally; several when
        a pool-rejected merged mixed-length group re-splits by the
        power-of-two length bucket so the dense fallback keeps the
        pre-paging ~2x pad bound instead of padding every ticker to the
        merged group's max.
        """
        from ..parallel import sweep as sweep_mod
        job0 = group[0]
        spec = self._FUSED_STRATEGIES[job0.strategy]
        ppy = job0.periods_per_year or 252
        cost = job0.cost
        h2d_hit = False
        m = None
        paged = False
        ragged = len(set(int(x) for x in lengths)) > 1
        if (allow_paged and self.use_paged
                and all(j.panel_digest for j in group)
                and self._fused_ops.paged_supported(job0.strategy)):
            paged_out = self._try_paged_submit(group, series, lengths,
                                               grid)
            paged = paged_out is not None
            if paged:
                # A fully pool-warm group skipped every upload: collect's
                # d2h span reports it exactly like a device-block h2d hit.
                m, h2d_hit = paged_out
            if m is None and ragged:
                buckets: dict[int, list[int]] = {}
                for i, j in enumerate(group):
                    b = (len(j.ohlcv) or j.panel_bytes_len).bit_length()
                    buckets.setdefault(b, []).append(i)
                if len(buckets) > 1:
                    log.warning(
                        "jobs %s (%s): page pool rejected the merged "
                        "group; re-splitting into %d dense length "
                        "buckets", [j.id for j in group], job0.strategy,
                        len(buckets))
                    out = []
                    sub_t0 = t0
                    for _, idx in sorted(buckets.items()):
                        out.extend(self._submit_fused_group(
                            [group[i] for i in idx],
                            [series[i] for i in idx],
                            [lengths[i] for i in idx], axes, grid,
                            sub_t0, allow_paged=False))
                        # Later buckets restart the clock (the split
                        # disciplines' rule: one subset's submit wall
                        # must not re-attribute to the next).
                        sub_t0 = time.perf_counter()
                    return out
        self._observe_substrates(job0.strategy)
        if m is None:
            if not ragged:
                arrays, h2d_hit = self._uniform_field_arrays(
                    group, series, spec.fields)
                t_real = None
            else:
                # Column-wise stack (pad_and_stack would also pad the
                # unused fields — wasted memcpy on the hot path).
                t_max = int(max(lengths))
                arrays = [_stack_field_ragged(series, t_max, f)
                          for f in spec.fields]
                t_real = np.asarray(lengths, np.int32)
                self._c_pad_bars["dense"].inc(
                    int(sum(t_max - int(t) for t in lengths)))
            if self._mesh is not None:
                run = spec.run

                def runner(*a, run=run, grid=grid, cost=cost, ppy=ppy):
                    return run(*a[:-1], grid, cost, ppy, a[-1])

                m = self._mesh_call(
                    ("fused",) + self._group_key(job0, axes),
                    runner, arrays, t_real)
            else:
                m = spec.run(*arrays, grid, cost, ppy, t_real)
        # paged implies mesh is None, so the suffix is vacuous there.
        route = (("paged" if paged else "fused")
                 + ("_mesh" if self._mesh is not None else ""))
        # Shape in the cold key: jit compiles per (rows, bars), so a new
        # group size IS a compile, not an execute.
        self._observe_submit(
            job0.strategy, route, t0,
            cold_key=(route, len(group), int(max(lengths)))
            + self._group_key(job0, axes), group=group,
            bars=int(max(lengths)),
            combos=sweep_mod.grid_size(grid) if grid else 1)
        return [self._finish_group(group, m, t0, len(group), job0,
                                   h2d_hit=h2d_hit)]

    def _submit_best_returns_group(self, group, series, lengths, t0):
        """Fleet-portfolio jobs (proto ``JobSpec.best_returns``): sweep the
        grid, pick each job's best combo by ``rank_metric`` (NaN-last,
        direction-aware — ``sweep.best_params``'s discipline), re-price the
        winner, and ship a DBXP block: grid index + 9 metric values + the
        per-bar net-return series. Sweep -> selection -> repricing run in
        ONE jitted trace per group (the ``sweep_and_compose`` discipline:
        the (n, P) intermediates never leave the device); the three result
        arrays start async d2h copies so the next batch overlaps.

        Uses the generic sweep path (the repricing needs positions, which
        the fused kernels do not materialize); selection is identical
        either way.
        """
        import jax.numpy as jnp

        from ..ops.metrics import Metrics

        job0 = group[0]
        axes = wire.grid_from_proto(job0.grid)
        metric = job0.rank_metric or "sharpe"
        if metric not in Metrics._fields:
            # Validated-bad, the _topk_request_ok discipline: a hand-built
            # spec naming an unknown metric completes empty with a loud
            # error instead of crashing the worker inside the trace.
            log.error("jobs %s: unknown best_returns rank metric %r; "
                      "completing empty", [j.id for j in group], metric)
            return (list(group), None, t0, 0, None)
        batch, _, mask = data_mod.pad_and_stack(series)
        panel_arrays = [np.asarray(f) for f in batch]
        fn = self._best_returns_fn(job0, axes, metric)
        m_best, idx, returns = fn(
            type(batch)(*(jnp.asarray(a) for a in panel_arrays)),
            jnp.asarray(mask))
        stacked = _start_result_copy(m_best)
        for arr in (idx, returns):
            try:
                arr.copy_to_host_async()
            except AttributeError:
                pass
        return (list(group), stacked, t0, len(group),
                {"kind": "returns", "idx": idx, "returns": returns,
                 "metric": metric, "lens": lengths})

    def _best_returns_fn(self, job0, axes, metric: str):
        """Build (and cache) the one-trace sweep->select->reprice function
        for a (strategy, grid, cost, ppy, metric) signature."""
        import jax
        import jax.numpy as jnp

        from ..models import base as models_base
        from ..ops import pnl as pnl_mod
        from ..ops.metrics import Metrics
        from ..parallel import sweep as sweep_mod

        key = (("best_returns",) + self._group_key(job0, axes) + (metric,))
        fn = self._mesh_fns.get(key)   # shared FIFO-evicted compile cache
        if fn is not None:
            return fn

        strategy = models_base.get_strategy(job0.strategy)
        cost = job0.cost
        ppy = job0.periods_per_year or 252
        grid = {k: jnp.asarray(v)
                for k, v in sweep_mod.product_grid(**axes).items()}

        @jax.jit
        def f(panel, bar_mask):
            m = sweep_mod.run_sweep(panel, strategy, grid, cost=cost,
                                    bar_mask=bar_mask,
                                    periods_per_year=ppy)
            # Selection delegates to THE shared implementation
            # (sweep.best_params: NaN-last, direction-aware) so this path
            # can never drift from walk-forward/portfolio selection.
            _, chosen, idx = sweep_mod.best_params(
                getattr(m, metric), grid, metric=metric, return_index=True)
            idx = idx.astype(jnp.int32)                          # (n,)

            def per_ticker(o1, mask1, p1):
                pos = strategy.positions(o1, p1)
                # run_sweep's padding discipline: HOLD the last valid
                # position through padded bars (zero return, zero
                # turnover on repeat-last closes).
                last_idx = jnp.maximum(
                    jnp.sum(mask1.astype(jnp.int32), axis=-1) - 1, 0)
                pos_last = jnp.take(pos, last_idx, axis=-1)
                return jnp.where(mask1, pos, pos_last)

            pos = jax.vmap(per_ticker)(panel, bar_mask, chosen)
            res = pnl_mod.backtest_prefix(panel.close, pos, cost=cost)
            m_best = Metrics(*(jnp.take_along_axis(f_, idx[:, None], axis=1)
                               for f_ in m))                     # (n, 1)
            return m_best, idx, res.returns

        if len(self._mesh_fns) >= self._MESH_FN_CAP:
            self._evict_mesh_fn()
        self._mesh_fns[key] = f
        return f

    def _submit_walkforward_group(self, group, series, lengths, t0):
        """Walk-forward jobs (proto ``JobSpec.wf_*``): per refit window,
        train-span sweep -> per-ticker argmax by ``wf_metric`` ->
        out-of-sample repricing on the next ``wf_test`` bars; the DBXM
        result is ONE stitched OOS metrics row per job, not a per-combo
        matrix. Jobs too short for a single train+test window complete
        with an empty block and a loud error. Uniform groups shard over
        the chip mesh (the refit scan's carries are per-ticker, so rows
        are independent); ragged groups refit per job single-device."""
        import logging

        import jax.numpy as jnp

        from ..models import base as models_base
        from ..ops.metrics import Metrics
        from ..parallel import sweep as sweep_mod, walkforward

        log = logging.getLogger("dbx.compute")
        job0 = group[0]
        need = job0.wf_train + job0.wf_test
        metric = job0.wf_metric or "sharpe"
        if metric not in Metrics._fields:
            # Validated-bad, like a malformed pairs leg: raising here would
            # requeue the group through lease expiry forever.
            log.error("walk-forward jobs %s request unknown selection "
                      "metric %r (known: %s); completing with empty metrics",
                      [j.id for j in group], metric,
                      ", ".join(Metrics._fields))
            return (list(group), None, t0, 0, None)
        good, bad = [], []
        for j, s, n_bars in zip(group, series, lengths):
            if job0.wf_test <= 0 or n_bars < need:
                log.error(
                    "walk-forward job %s needs wf_test > 0 and >= %d bars "
                    "(train %d + test %d), has %d; completing with empty "
                    "metrics", j.id, need, job0.wf_train, job0.wf_test,
                    n_bars)
                bad.append(j)
            else:
                good.append((j, s))
        if not good:
            return (bad, None, t0, 0, None)

        axes = wire.grid_from_proto(job0.grid)
        grid = sweep_mod.product_grid(
            **{k: jnp.asarray(v) for k, v in axes.items()})
        strategy = models_base.get_strategy(job0.strategy)
        kwargs = dict(train=job0.wf_train, test=job0.wf_test,
                      metric=metric, cost=job0.cost,
                      periods_per_year=job0.periods_per_year or 252)
        uniform = len({s.n_bars for _, s in good}) == 1
        panel_cls = type(good[0][1])
        if uniform:
            arrays = [np.stack([np.asarray(getattr(s, f)) for _, s in good])
                      for f in good[0][1]._fields]
        # Fused-train route (VERDICT r4 item 4): when the grid is large
        # enough that the per-window train sweep dominates, run phase 1 on
        # the fused Pallas kernel — walk_forward_fused's two-phase split
        # (one stacked train sweep for ALL refit windows, then re-price
        # only each ticker's chosen param). The generic single-program
        # walk_forward wins below the threshold (bench: 11.5M/s generic vs
        # 5.5M/s fused at P=400), so routing is by grid size, with the
        # same fused eligibility table and rounding-twin caveats as the
        # plain sweep path (train span plays the role of the bar count).
        fused_wf = (self.use_fused and uniform
                    and sweep_mod.grid_size(grid) >=
                    self._WF_FUSED_MIN_COMBOS
                    and self._fused_demotion_reason(
                        job0, axes, [job0.wf_train]) is None)
        if fused_wf:
            spec = self._FUSED_STRATEGIES[job0.strategy]
            prod_np = {k: np.asarray(v)
                       for k, v in sweep_mod.product_grid(**axes).items()}
            cost = job0.cost
            ppy = kwargs["periods_per_year"]

            def train_fn(*fs):
                return spec.run(*fs, prod_np, cost, ppy, None)

            log.info("walk-forward jobs %s (%s, P=%d) using the "
                     "fused-train route", [j.id for j, _ in good],
                     job0.strategy, sweep_mod.grid_size(grid))
            self._observe_substrates(job0.strategy)
            if self._mesh is not None:
                def runner(*blks):
                    r = walkforward.walk_forward_fused(
                        panel_cls(*blks[:-1]), strategy, dict(grid),
                        train_fn, fields=spec.fields, **kwargs)
                    return Metrics(*(f[:, None] for f in r.oos_metrics))

                m = self._mesh_call(
                    ("wf-fused",) + self._group_key(job0, axes)
                    + (job0.wf_train, job0.wf_test, metric),
                    runner, arrays, None)
                return ([j for j, _ in good] + bad, _start_result_copy(m),
                        t0, len(good), None)
            panel = panel_cls(*(jnp.asarray(a) for a in arrays))
            m = walkforward.walk_forward_fused(
                panel, strategy, dict(grid), train_fn, fields=spec.fields,
                **kwargs).oos_metrics
            m = Metrics(*(f[:, None] for f in m))   # one OOS row per job
            return ([j for j, _ in good] + bad, _start_result_copy(m), t0,
                    len(good), None)
        if uniform and self._mesh is not None:
            # The per-window refit is row-parallel (per-ticker scan +
            # argmax, no cross-row interaction), so walk-forward groups
            # shard over the chip mesh like any sweep. The runner returns
            # (rows, 1) metric columns so the row-sharded out_specs fit.
            def runner(*blks):
                r = walkforward.walk_forward(
                    panel_cls(*blks[:-1]), strategy, dict(grid), **kwargs)
                return Metrics(*(f[:, None] for f in r.oos_metrics))

            m = self._mesh_call(
                ("wf",) + self._group_key(job0, axes)
                + (job0.wf_train, job0.wf_test, metric),
                runner, arrays, None)
            return ([j for j, _ in good] + bad, _start_result_copy(m), t0,
                    len(good), None)
        if uniform:
            panel = panel_cls(*(jnp.asarray(a) for a in arrays))
            m = walkforward.walk_forward(panel, strategy, dict(grid),
                                         **kwargs).oos_metrics
        else:
            # Window starts are global bar indices: ragged histories can't
            # share one scan, so they refit per job (grouping buckets
            # lengths by power of two, keeping this rare and bounded).
            rows = [walkforward.walk_forward(
                type(s)(*(jnp.asarray(np.asarray(f))[None, :] for f in s)),
                strategy, dict(grid), **kwargs).oos_metrics
                for _, s in good]
            m = Metrics(*(jnp.concatenate(f, axis=0) for f in zip(*rows)))
        m = Metrics(*(f[:, None] for f in m))   # one OOS row per job
        return ([j for j, _ in good] + bad, _start_result_copy(m), t0,
                len(good), None)

    def _submit_pairs_group(self, group, t0):
        """Two-legged jobs: stack both legs, run the pairs sweep.

        The fused pairs kernel takes per-pair ragged lengths; on CPU the
        generic path has no mask support, so ragged groups fall back to a
        per-job loop (grouping already buckets lengths by power of two, so
        this is rare and bounded).
        """
        import logging

        import jax.numpy as jnp

        from ..models import pairs as pairs_mod
        from ..parallel import sweep as sweep_mod

        log = logging.getLogger("dbx.compute")
        # Per-job validation at decode time: a malformed pair (missing
        # second leg, or legs of different lengths — padding one leg would
        # fabricate bars the PnL treats as real) is completed with an EMPTY
        # metric block and a loud error rather than poisoning the whole
        # co-batched group or looping forever through lease requeues.
        job0 = group[0]
        wf = job0.wf_train > 0
        if wf:
            # Validate the walk-forward request once for the group (the
            # same gates as the single-asset path).
            metric = job0.wf_metric or "sharpe"
            from ..ops.metrics import Metrics

            if job0.wf_test <= 0 or metric not in Metrics._fields:
                log.error(
                    "pairs walk-forward jobs %s need wf_test > 0 and a "
                    "known metric (got test=%d, metric=%r); completing "
                    "with empty metrics", [j.id for j in group],
                    job0.wf_test, metric)
                return (list(group), None, t0, 0, None)
        good, bad = [], []
        trace_pairs = obs.job_trace_pairs(group)
        t0_wall = time.time()
        t_dec = time.perf_counter()
        hits = 0
        for j in group:
            if not j.ohlcv2 and not j.panel_digest2:
                log.error("pairs job %s has no second leg (ohlcv2); "
                          "completing with empty metrics", j.id)
                bad.append(j)
                continue
            y, hit_y = self._resolve_series(j)
            x, hit_x = self._resolve_series(j, leg2=True)
            if y.n_bars != x.n_bars:
                log.error("pairs job %s legs differ in length (%d vs "
                          "%d); completing with empty metrics", j.id,
                          y.n_bars, x.n_bars)
                bad.append(j)
                continue
            if wf and y.n_bars < job0.wf_train + job0.wf_test:
                log.error(
                    "pairs walk-forward job %s needs >= %d bars "
                    "(train %d + test %d), has %d; completing with "
                    "empty metrics",
                    j.id, job0.wf_train + job0.wf_test, job0.wf_train,
                    job0.wf_test, y.n_bars)
                bad.append(j)
                continue
            hits += 1 if (hit_y and hit_x) else 0
            good.append((j, y, x))
        dur = time.perf_counter() - t_dec
        self._h_decode.observe(dur)
        obs.emit_span("worker.decode", t0_wall, dur, pairs=trace_pairs,
                      jobs=len(group),
                      cache_hit=bool(good) and hits == len(good),
                      cache_hits=hits)
        self._c_decode_bytes.inc(
            sum(len(j.ohlcv) + len(j.ohlcv2) for j in group))
        if not good:
            return (bad, None, t0, 0, None)
        group = [j for j, _, _ in good]
        ys = [y for _, y, _ in good]
        xs = [x for _, _, x in good]
        axes = wire.grid_from_proto(group[0].grid)
        grid = sweep_mod.product_grid(**axes)
        ppy = group[0].periods_per_year or 252
        cost = group[0].cost
        lens = np.asarray([y.n_bars for y in ys], np.int32)
        t_max = int(lens.max())
        y_close = _stack_field_ragged(ys, t_max)
        x_close = _stack_field_ragged(xs, t_max)
        uniform = len(set(int(v) for v in lens)) == 1
        if wf:
            # Walk-forward pairs (JobSpec.wf_* + strategy "pairs"): one
            # stitched OOS metrics row per job, like the single-asset path.
            # Window starts are global bar indices, so ragged groups refit
            # per job (grouping buckets lengths by power of two — rare).
            from ..ops.metrics import Metrics
            from ..parallel import walkforward

            kwargs = dict(train=job0.wf_train, test=job0.wf_test,
                          metric=job0.wf_metric or "sharpe", cost=cost,
                          periods_per_year=ppy)
            if uniform and self._mesh is not None:
                # Row-parallel exactly like the single-asset wf path: the
                # per-window refit has no cross-pair interaction, so
                # uniform groups shard over the chip mesh.
                def runner(yb, xb, tr):
                    r = walkforward.walk_forward_pairs(yb, xb, dict(grid),
                                                       **kwargs)
                    return Metrics(*(f[:, None] for f in r.oos_metrics))

                m = self._mesh_call(
                    ("pairs-wf",) + self._group_key(job0, axes)
                    + (job0.wf_train, job0.wf_test, kwargs["metric"]),
                    runner, [y_close, x_close], None)
                return self._finish_group(list(group) + bad, m, t0,
                                          len(group), job0)
            if uniform:
                m = walkforward.walk_forward_pairs(
                    jnp.asarray(y_close), jnp.asarray(x_close), dict(grid),
                    **kwargs).oos_metrics
            else:
                rows = [walkforward.walk_forward_pairs(
                    jnp.asarray(y_close[i:i + 1, :int(lens[i])]),
                    jnp.asarray(x_close[i:i + 1, :int(lens[i])]),
                    dict(grid), **kwargs).oos_metrics
                    for i in range(len(group))]
                m = Metrics(*(jnp.concatenate(f, axis=0)
                              for f in zip(*rows)))
            m = Metrics(*(f[:, None] for f in m))   # one OOS row per job
            return self._finish_group(list(group) + bad, m, t0,
                                      len(group), job0)
        lb = np.asarray(grid.get("lookback", np.empty(0)))
        n_lb = int(np.unique(np.round(lb)).size)
        demotion = None
        if lb.size == 0:
            demotion = "no 'lookback' axis in grid"
        elif not np.allclose(lb, np.round(lb)):
            demotion = "non-integral lookback values"
        elif n_lb > self._FUSED_MAX_WINDOWS:
            demotion = (f"{n_lb} distinct lookbacks exceed the kernel cap "
                        f"of {self._FUSED_MAX_WINDOWS}")
        elif t_max > self._FUSED_MAX_BARS:
            demotion = (f"{t_max} bars exceed the kernel VMEM cap of "
                        f"{self._FUSED_MAX_BARS}")
        if ((not self.use_fused or demotion is not None)
                and self._mesh is not None and uniform
                and t_max > self._FUSED_MAX_BARS
                and len(group) < self._mesh.devices.size):
            # Long-context pairs: shard the bar axis over the chips (the
            # single-asset _submit_timeshard_groups discipline; ragged
            # groups keep the per-job generic loop — they cannot share
            # one padded panel). Grid gates are the SHARED helper.
            ts_reason = ("no 'lookback' axis in grid" if lb.size == 0
                         else self._timeshard_window_reason(
                             lb, int(np.asarray(grid["lookback"]).size),
                             t_max, what="lookback"))
            if ts_reason is None:
                log.info(
                    "jobs %s (pairs) routed to the time-sharded "
                    "long-context path (%d bars over %d chips)",
                    [j.id for j in group], t_max,
                    self._mesh.devices.size)
                return self._submit_pairs_timeshard(
                    group, bad, ys, xs, t_max, t0, axes, grid)
            log.warning(
                "jobs %s (pairs) are long-context (%d bars) but not "
                "time-shardable (%s); falling through to the generic "
                "path", [j.id for j in group], t_max, ts_reason)
        if self.use_fused and demotion is not None:
            log.warning("jobs %s (pairs) demoted to the generic path: %s",
                        [j.id for j in group], demotion)
        if self.use_fused and demotion is None:
            from ..ops import fused

            self._observe_substrates("pairs")
            plb = np.asarray(grid["lookback"])
            pze = np.asarray(grid["z_entry"])
            pzx = (np.asarray(grid["z_exit"]) if "z_exit" in grid else 0.0)
            t_real = None if uniform else lens
            if self._mesh is not None:
                def runner(yb, xb, tr):
                    return fused.fused_pairs_sweep(
                        yb, xb, plb, pze, z_exit=pzx, t_real=tr, cost=cost,
                        periods_per_year=ppy)

                m = self._mesh_call(
                    ("pairs-fused",) + self._group_key(group[0], axes),
                    runner, [y_close, x_close], t_real)
            else:
                m = fused.fused_pairs_sweep(
                    y_close, x_close, plb, pze, z_exit=pzx, t_real=t_real,
                    cost=cost, periods_per_year=ppy)
        elif uniform:
            if self._mesh is not None:
                def runner(yb, xb, tr):
                    return pairs_mod.run_pairs_sweep(
                        yb, xb, dict(grid), cost=cost, periods_per_year=ppy)

                m = self._mesh_call(
                    ("pairs-generic",) + self._group_key(group[0], axes),
                    runner, [y_close, x_close], None)
            else:
                m = pairs_mod.run_pairs_sweep(
                    jnp.asarray(y_close), jnp.asarray(x_close), dict(grid),
                    cost=cost, periods_per_year=ppy)
        else:
            rows = [pairs_mod.run_pairs_sweep(
                jnp.asarray(y_close[i:i + 1, :int(lens[i])]),
                jnp.asarray(x_close[i:i + 1, :int(lens[i])]), dict(grid),
                cost=cost, periods_per_year=ppy)
                for i in range(len(group))]
            m = type(rows[0])(*(jnp.concatenate(f, axis=0)
                                for f in zip(*rows)))
        return self._finish_group(list(group) + bad, m, t0, len(group),
                                  group[0])

    def _submit_pairs_timeshard(self, group, bad, ys, xs, t, t0,
                                axes, grid):
        """Uniform long-context pairs group: both legs' bar axes sharded
        over the chip mesh via ``timeshard.sharded_pairs_backtest``, one
        sub-backtest per grid combo (the ``_submit_timeshard_groups``
        discipline applied to the two-legged panel). Legs re-stack
        through ``_stack_field_ragged`` so the repeat-last padding (the
        t_real dead-bar contract) stays the one shared implementation."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops.metrics import Metrics
        from ..parallel import timeshard

        job0 = group[0]
        tmesh = self._time_mesh()
        n_dev = tmesh.devices.size
        T_pad = -(-t // n_dev) * n_dev
        cost = float(job0.cost)
        ppy = int(job0.periods_per_year or 252)
        lbs = np.asarray(grid["lookback"])
        zes = np.asarray(grid["z_entry"])
        zxs = (np.asarray(grid["z_exit"]) if "z_exit" in grid
               else np.zeros_like(zes))
        combos = tuple(
            (int(round(float(lbs[i]))), float(zes[i]), float(zxs[i]))
            for i in range(lbs.size))

        sharding = NamedSharding(tmesh, P(None, timeshard.TIME_AXIS))
        y = jax.device_put(_stack_field_ragged(ys, T_pad), sharding)
        x = jax.device_put(_stack_field_ragged(xs, T_pad), sharding)
        t_real = None if t == T_pad else t
        key = (("timeshard-pairs",) + self._group_key(job0, axes)
               + (t, T_pad))
        run = self._mesh_fns.get(key)
        if run is None:
            def run(yb, xb, _tr=t_real):
                ms = [timeshard.sharded_pairs_backtest(
                          tmesh, yb, xb, lkb, ze, z_exit=zx, cost=cost,
                          periods_per_year=ppy,
                          axis_name=timeshard.TIME_AXIS, t_real=_tr)
                      for (lkb, ze, zx) in combos]
                return Metrics(*(jnp.stack(cols, axis=-1)
                                 for cols in zip(*ms)))

            run = jax.jit(run)
            if len(self._mesh_fns) >= self._MESH_FN_CAP:
                self._evict_mesh_fn()
            self._mesh_fns[key] = run
        return self._finish_group(list(group) + bad, run(y, x), t0,
                                  len(group), job0)

    def collect(self, pending) -> list[Completion]:
        """Block for a submitted batch's results and pack completions."""
        from ..ops.metrics import Metrics

        out: list[Completion] = []
        for entry in pending:
            # Entries are 5-tuples from the legacy paths and 6-tuples from
            # _finish_group (the trailing h2d_hit flag).
            group, stacked, t0, n_real, extra = entry[:5]
            h2d_hit = bool(entry[5]) if len(entry) > 5 else False
            t_wait = time.perf_counter()
            if stacked is None:
                host = None
            else:
                # The blocking device drain, traced per group: the d2h
                # stage of each job's timeline (the worker.collect span
                # above it covers the whole pending entry). cache_hit here
                # reports that the SUBMIT-side panel upload was served
                # from the device digest cache (no h2d for this group's
                # panels); the drain itself is real work either way.
                with obs.trace_context(obs.job_trace_pairs(group)), \
                        obs.span("worker.d2h", jobs=len(group),
                                 cache_hit=h2d_hit):
                    host = np.asarray(stacked)
            if host is not None:
                # The blocking d2h drain: everything after here is host-side
                # packing. Combo credit counts only real jobs (mesh pad rows
                # are compute, not results) and is derived from each job's
                # GRID, not the result shape — a top-k/best_returns group
                # ships k (or 1) rows but computed the full grid, and the
                # dispatcher's backtests_per_sec credits grid combos too
                # (the two gauges must agree).
                self._h_collect.observe(time.perf_counter() - t_wait)
                self._c_d2h_bytes.inc(host.nbytes)
                n_rows = min(host.shape[1], n_real)
                combos = sum(wire.grid_n_combos(job.grid)
                             for job in group[:n_rows])
                self._c_backtests.inc(combos)
                self._bt_rate.add(combos)
            idx_host = ret_host = lens = None
            mode = None
            if isinstance(extra, dict):          # best_returns (DBXP) group
                mode = extra["kind"]
                idx_host = np.asarray(extra["idx"])
                ret_host = np.asarray(extra["returns"])
                lens = extra["lens"]
            elif extra is not None:              # top-k (DBXS) group
                mode = "topk"
                idx_host = np.asarray(extra[0])
            elapsed = time.perf_counter() - t0
            per_job = elapsed / max(len(group), 1)
            # n_real (the jobs actually computed), NOT host.shape[1]: the
            # mesh path pads rows to a chip multiple, and a pad row must
            # never be reported as a validated-bad job's "result".
            n_rows = 0 if host is None else min(host.shape[1], n_real)
            for i, job in enumerate(group):
                if i < n_rows:
                    row = Metrics(*(host[k, i] for k in range(9)))
                    if mode == "topk":
                        blob = wire.topk_to_bytes(idx_host[i], row, extra[1])
                    elif mode == "returns":
                        # Trim to the job's real history: padded bars earn
                        # exactly zero (repeat-last close + held position)
                        # but belong to the group, not the job.
                        blob = wire.best_returns_to_bytes(
                            int(idx_host[i]), row,
                            ret_host[i, :int(lens[i])], extra["metric"])
                    else:
                        blob = wire.metrics_to_bytes(row)
                else:
                    blob = b""   # validated-bad job: complete, no result
                out.append(Completion(job.id, blob, per_job,
                                      trace_id=job.trace_id))
        return out

    def process(self, jobs) -> list[Completion]:
        return self.collect(self.submit(jobs))


def _timeshard_window_reason(wins, n_combos: int, t_min: int, n_dev: int, *,
                             halo_bound: bool = True,
                             what: str = "window") -> str | None:
    """Shared grid gates of EVERY time-sharded route (single-asset,
    pairs, and the slice worker — one implementation so they cannot
    drift): per-combo compile cap, integral windows >= 1, and the
    halo-fits-one-per-chip-block bound."""
    wins = np.asarray(wins, np.float64)
    if n_combos == 0 or wins.size == 0:
        return "empty grid"
    if n_combos > JaxSweepBackend._TIMESHARD_MAX_COMBOS:
        return (f"{n_combos} grid combos exceed the per-combo compile "
                f"cap of {JaxSweepBackend._TIMESHARD_MAX_COMBOS}")
    if not np.allclose(wins, np.round(wins)):
        return f"non-integral {what} values"
    if wins.min() < 1:
        return f"{what} values below 1"
    if halo_bound:
        block = -(-int(t_min) // n_dev)
        if int(wins.max()) > block:
            return (f"max {what} {int(wins.max())} exceeds the "
                    f"{block}-bar per-chip block; the halo exchange "
                    "needs the window to fit one neighbor block")
    return None


def timeshard_route_reason(strategy: str, axes, lengths,
                           n_dev: int) -> str | None:
    """None when a long-context single-asset group can route to the
    time-sharded backtests over an ``n_dev``-chip time axis; otherwise
    why it stays on the generic path. Shared by the single-host backend
    (``JaxSweepBackend._timeshard_reason``) and the slice worker."""
    from ..parallel import sweep as sweep_mod

    fam = JaxSweepBackend._TIMESHARD_STRATEGIES.get(strategy)
    if fam is None:
        return f"strategy {strategy!r} has no time-sharded backtest"
    if set(axes) != set(fam.params):
        return (f"grid axes {sorted(axes)} do not match the "
                f"time-sharded contract {sorted(fam.params)}")
    prod = sweep_mod.product_grid(**axes)
    n_combos = int(np.asarray(next(iter(prod.values()))).size)
    int_axes = JaxSweepBackend._FUSED_STRATEGIES[strategy].window_axes
    wins = np.concatenate(
        [np.asarray(axes[a], np.float64) for a in int_axes])
    reason = _timeshard_window_reason(
        wins, n_combos, min(lengths), n_dev, halo_bound=fam.halo_bound,
        what=f"window ({'/'.join(int_axes)})")
    if reason is not None:
        return reason
    if strategy == "sma_crossover":
        f_ = np.round(np.asarray(prod["fast"], np.float64))
        s_ = np.round(np.asarray(prod["slow"], np.float64))
        if (f_ >= s_).any():
            return "grid contains fast >= slow combos"
    if strategy in ("donchian", "donchian_hl", "stochastic"):
        # The generic channel paths poison windows beyond MAX_WINDOW to
        # NaN; keep those semantics-defining results (the fused demotion
        # rule, applied identically here).
        from ..models import donchian as donchian_mod
        from ..models import stochastic as stoch_mod

        bound = (stoch_mod.MAX_WINDOW if strategy == "stochastic"
                 else donchian_mod.MAX_WINDOW)
        if float(wins.max()) > bound:
            return (f"max window {int(wins.max())} exceeds the channel "
                    f"view bound {bound}")
    return None


def timeshard_combos(strategy: str, axes) -> tuple:
    """The per-combo static parameter tuples of a time-sharded sweep, in
    DBXM (product_grid) column order — ints for window axes, floats
    otherwise. Shared by the single-host backend and the slice worker so
    the combo order cannot drift from the metric-column contract."""
    from ..parallel import sweep as sweep_mod

    fam = JaxSweepBackend._TIMESHARD_STRATEGIES[strategy]
    prod = sweep_mod.product_grid(**axes)
    int_axes = set(JaxSweepBackend._FUSED_STRATEGIES[strategy].window_axes)
    n_combos = int(np.asarray(next(iter(prod.values()))).size)
    return tuple(
        tuple(int(round(float(np.asarray(prod[p])[i])))
              if p in int_axes else float(np.asarray(prod[p])[i])
              for p in fam.params)
        for i in range(n_combos))


class InstantBackend:
    """Completes every job immediately with an empty metric block (tests)."""

    chips = 1

    def __init__(self):
        self.seen: list[str] = []

    def process(self, jobs) -> list[Completion]:
        out = []
        from ..ops.metrics import Metrics
        empty = wire.metrics_to_bytes(
            Metrics(*(np.zeros(1, np.float32) for _ in Metrics._fields)))
        for job in jobs:
            self.seen.append(job.id)
            out.append(Completion(job.id, empty, 0.0,
                                  trace_id=job.trace_id))
        return out


class SleepBackend:
    """Fixed per-job delay — the reference stub's behavior, for liveness tests."""

    chips = 1

    def __init__(self, delay_s: float = 0.05):
        self.delay_s = delay_s

    def process(self, jobs) -> list[Completion]:
        out = []
        for job in jobs:
            time.sleep(self.delay_s)
            out.append(Completion(job.id, b"", self.delay_s,
                                  trace_id=job.trace_id))
        return out
