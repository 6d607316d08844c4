"""The worker: poll loop + compute thread, bridged by bounded queues.

Same shape as the reference's worker — an I/O loop polling the dispatcher on
a tick, a dedicated compute thread so device-bound work never starves the
control plane, and bounded channels between them (reference
``src/worker/main.rs:24-85``) — with its sharp edges removed:

- the worker stops *requesting* jobs while its compute queue is full (the
  reference kept polling every 250 ms regardless, hoarding up to 1024
  batches in its channel; reference ``src/worker/handlers.rs:54-58``);
- a failed completion RPC is retried with backoff, not ``.unwrap()``-panicked
  (reference ``src/worker/main.rs:82``);
- startup connect failures retry instead of exiting (reference
  ``src/worker/main.rs:50-55``);
- shutdown is graceful: in-flight work drains before exit (a reference
  Limitations item, reference ``README.md:85``).

Round 14 adds the **pipelined executor**: for two-phase (submit/collect)
backends the compute side runs as a bounded two-thread pipeline —
this module's submit thread decodes and launches batch N+1 while a
collector thread drains batch N's device results — with the control
loop prefetching payloads/compile-cache entries for batches still
queued behind the pipeline. ``DBX_PIPELINE=0`` falls back to the
strictly serial loop (the bit-identity reference); see
DESIGN.md "Pipelined executor (round 14)".
"""

from __future__ import annotations

import argparse
import logging
import os
import queue as queue_mod
import sys
import threading
import time
import uuid

import grpc

from . import backtesting_pb2 as pb
from . import compute, service
from .. import obs
from ..obs import fleet as obs_fleet
from ..obs import flight as obs_flight
from ..runtime import _core as native_core

log = logging.getLogger("dbx.worker")


class _Channel:
    """Bounded channel bridging the control and compute threads.

    Backed by the native C++ MPMC queue when the core is available — the
    role flume's bounded channels play in the reference worker (reference
    ``src/worker/main.rs:32-42``; SURVEY.md §2.2 native ledger) — and by
    ``queue.Queue`` otherwise. Items cross the boundary as proto bytes via
    the ``enc``/``dec`` pair, so the native queue stays a plain blob queue.

    Capacity semantics: the native queue is always bounded, so an
    "unbounded" channel gets the ``_UNBOUNDED`` sentinel capacity — past it
    the producer *blocks* (backpressure), whereas the pure-Python
    ``queue.Queue(0)`` fallback never would. At 2^20 undrained completions
    that divergence only triggers after the control thread has been wedged
    for far longer than the dispatcher's prune window, at which point
    backpressure on the compute thread is the safer behavior anyway.
    """

    _UNBOUNDED = 1 << 20

    def __init__(self, capacity: int | None, enc, dec):
        self._enc, self._dec = enc, dec
        self._capacity = capacity
        self._nq = None
        if native_core.available():
            try:
                self._nq = native_core.NativeQueue(
                    capacity or self._UNBOUNDED)
            except RuntimeError:
                self._nq = None
        self._pq: queue_mod.Queue | None = (
            None if self._nq is not None else queue_mod.Queue(capacity or 0))
        self.backend = "native" if self._nq is not None else "python"

    def put(self, item) -> None:
        if self._nq is not None:
            self._nq.push(self._enc(item))
        else:
            self._pq.put(item)

    def get(self):
        if self._nq is not None:
            return self._dec(self._nq.pop())
        return self._pq.get()

    def get_nowait(self):
        if self._nq is not None:
            b = self._nq.pop(timeout_ms=0)
            if b is None:
                raise queue_mod.Empty
            return self._dec(b)
        return self._pq.get_nowait()

    def full(self) -> bool:
        if self._nq is not None:
            return self._capacity is not None and len(self._nq) >= self._capacity
        return self._pq.full()

    def empty(self) -> bool:
        if self._nq is not None:
            return len(self._nq) == 0
        return self._pq.empty()

    def depth(self) -> int:
        """Approximate occupancy (observability gauge; racy by nature)."""
        if self._nq is not None:
            return len(self._nq)
        return self._pq.qsize()


def _pb_size(msg) -> int:
    """Serialized size of a proto message; 0 for test doubles that stand
    in for replies without implementing ByteSize."""
    size = getattr(msg, "ByteSize", None)
    return size() if callable(size) else 0


class _SyncLegFailed(Exception):
    """One tune-sync RPC leg failed (already counted under its own
    method label); the tick aborts and retries on the next interval."""


def pipeline_enabled() -> bool:
    """``DBX_PIPELINE`` (default on): run two-phase backends through the
    double-buffered submit/collect pipeline. ``0`` keeps the strictly
    serial loop — the bit-identity reference for the pipelined path.
    Read lazily (per worker run), never at import time."""
    return os.environ.get("DBX_PIPELINE", "1").lower() not in (
        "0", "off", "false")


def pipeline_depth() -> int:
    """``DBX_PIPELINE_DEPTH`` (default 2): submitted-but-uncollected
    batches the pipeline holds before the submit thread blocks. Depth 2
    is classic double buffering (one batch on device, one staging);
    deeper mostly grows queue wait, not overlap."""
    return max(int(os.environ.get("DBX_PIPELINE_DEPTH", "2")), 1)


def prefetch_enabled() -> bool:
    """``DBX_PREFETCH`` (default on): the control loop stages inputs for
    batches still queued behind the compute pipeline (payload decode,
    device page warm-up, compile-cache pull-forward)."""
    return os.environ.get("DBX_PREFETCH", "1").lower() not in (
        "0", "off", "false")


_BATCH_SENTINEL = b"S"


def _encode_batch(batch) -> bytes:
    if batch is None:
        return _BATCH_SENTINEL
    return b"B" + pb.JobsReply(jobs=batch).SerializeToString()


def _decode_batch(data: bytes):
    if data[:1] == _BATCH_SENTINEL:
        return None
    reply = pb.JobsReply()
    reply.ParseFromString(data[1:])
    return list(reply.jobs)


def _encode_completion(c: compute.Completion) -> bytes:
    # trace_id rides the channel envelope too: a completion crossing the
    # native MPMC queue must come out stitchable (proto CompleteRequest is
    # the envelope, so the wire field doubles as the channel field).
    return pb.CompleteRequest(
        id=c.job_id, metrics=c.metrics,
        elapsed_s=c.elapsed_s, trace_id=c.trace_id).SerializeToString()


def _decode_completion(data: bytes) -> compute.Completion:
    req = pb.CompleteRequest()
    req.ParseFromString(data)
    return compute.Completion(req.id, req.metrics, req.elapsed_s,
                              trace_id=req.trace_id)


class Worker:
    """Polls a dispatcher, runs a compute backend, reports completions."""

    def __init__(self, target: str, backend: compute.ComputeBackend, *,
                 worker_id: str | None = None,
                 poll_interval_s: float = 0.25,
                 status_interval_s: float = 1.0,
                 jobs_per_chip: int = 1,
                 max_inflight_batches: int = 2,
                 registry: "obs.Registry | None" = None):
        self.target = target
        self.backend = backend
        self.worker_id = worker_id or f"worker-{uuid.uuid4().hex[:8]}"
        self.poll_interval_s = poll_interval_s
        self.status_interval_s = status_interval_s
        self.jobs_per_chip = jobs_per_chip
        self._in = _Channel(max_inflight_batches, _encode_batch,
                            _decode_batch)
        self._out = _Channel(None, _encode_completion, _decode_completion)
        self._stop = threading.Event()
        self._busy = threading.Event()
        # Pipelined executor state (round 14): batches taken from the
        # channel but not yet fully collected. The counter (guarded by
        # its own lock — it is shared by the submit and collector
        # threads) drives the busy flag, so idle-exit and status
        # reporting see the WHOLE pipeline, not just the submit half.
        self._pipeline_lock = threading.Lock()
        self._pipeline_inflight = 0
        self._pipeline_done = threading.Event()
        # Compile-cache prefetch memo: (strategy, payload-size-bucket)
        # signatures whose arrival already pulled a tune-sync forward.
        self._prefetch_seen: set = set()
        # Backend warm-up runs on its own daemon thread (started lazily,
        # stopped in _shutdown): the page warm-up can upload device
        # pages — whose first-call scatter compile takes seconds per
        # pow2 shape class — and THIS thread owns the SendStatus
        # heartbeat; a stalled heartbeat gets a healthy worker pruned
        # mid-drain (the deferred-completion lesson).
        self._prefetch_q: queue_mod.Queue | None = None
        self._prefetch_thread: threading.Thread | None = None
        self._connected = True  # edge-triggered logging, reference CONNECTED
        self.jobs_completed = 0
        self.completions_dropped = 0
        self.batches_failed = 0   # backend exceptions (_backend_failed)
        self._compute_thread: threading.Thread | None = None
        # Failed completion RPCs park here with a due time instead of
        # sleep-retrying on the control thread (advisor finding: inline
        # backoff sleeps starved SendStatus past the dispatcher's prune
        # window, getting a healthy worker pruned mid-drain).
        self._deferred: list[tuple[float, int, compute.Completion]] = []
        self._next_status = 0.0
        # Observability: client-side RPC latency histograms + poll/error
        # counters (pre-resolved — the poll loop is a hot path), channel
        # occupancy and retry backlog as scrape-time gauges (labeled by
        # worker_id: several workers can share one process, e.g. bench's
        # control-plane saturation config).
        self.obs = registry or obs.get_registry()
        self._h_rpc = {
            m: self.obs.histogram("dbx_worker_rpc_seconds",
                                  help="worker-side RPC wall (incl. wire)",
                                  method=m)
            for m in ("RequestJobs", "SendStatus", "CompleteJobs",
                      "FetchPayload", "FetchCompiled", "OfferCompiled",
                      "GetStats")}
        self._c_rpc_errors = {
            m: self.obs.counter("dbx_worker_rpc_errors_total",
                                help="failed worker RPC attempts", method=m)
            for m in ("RequestJobs", "SendStatus", "CompleteJobs",
                      "FetchPayload", "FetchCompiled", "OfferCompiled",
                      "GetStats")}
        # Wire accounting (serialized proto bytes, pre-compression): the
        # bench's `wire_bytes_per_job` column and the dispatch-by-digest
        # A/B read these deltas.
        self._c_wire = {
            (m, d): self.obs.counter(
                "dbx_worker_wire_bytes_total",
                help="serialized proto bytes over worker RPCs",
                method=m, direction=d)
            for m in ("RequestJobs", "CompleteJobs", "FetchPayload")
            for d in ("request", "reply")}
        self._c_fetches = self.obs.counter(
            "dbx_worker_payload_fetches_total",
            help="FetchPayload recoveries for digest-only jobs")
        self._c_polls = self.obs.counter(
            "dbx_worker_polls_total", help="RequestJobs polls sent")
        self._c_idle_polls = self.obs.counter(
            "dbx_worker_idle_polls_total", help="polls answered empty")
        self._c_jobs_in = self.obs.counter(
            "dbx_worker_jobs_received_total", help="jobs received")
        self._c_dropped = self.obs.counter(
            "dbx_worker_completions_dropped_total",
            help="completions dropped after retry exhaustion")
        self._c_batch_failures = self.obs.counter(
            "dbx_worker_batch_failures_total",
            help="batches whose backend submit/collect raised")
        # Every per-worker-labeled metric (the collector-maintained
        # channel/deferred/busy gauges) is created in run() and removed
        # in its finally — a constructed-but-never-run Worker must leak
        # neither a collector closing over itself nor a uuid-labeled
        # gauge child.
        self._gauges: dict | None = None
        # Substrate-autotuner + fleet-compile-cache sync (tune/, round
        # 11): attached in run() only for backends that expose a schedule
        # registry (the jax backend) — the instant/sleep fakes neither
        # tune nor compile. New local schedule entries piggyback on
        # JobsRequest.schedule_json (zero-cost when clean); the pull leg
        # (fleet registry via GetStats + compile-cache exchange) runs on
        # its own tick — 10s default: schedules and compiles change on
        # first-contact timescales, and each GetStats makes the
        # dispatcher build its full obs summary.
        self.tune_sync_interval_s = 10.0
        self._compile_sync = None
        self._next_tune_sync = 0.0
        # Fleet telemetry gossip (obs/fleet.py, round 15): one compact
        # frame per poll on JobsRequest.telemetry_json when something
        # changed (or the heartbeat elapsed) — built in run() so the
        # generation id marks THIS run, DBX_FLEET_TELEMETRY=0 disables.
        self._telemetry: "obs_fleet.WorkerTelemetry | None" = None

    def _telemetry_stats(self) -> dict:
        """Counter snapshot for the fleet telemetry frame (obs/fleet.py
        reads through this hook instead of reaching into worker
        internals). The inflight read takes the pipeline lock — the same
        leaf lock the busy flag rides."""
        with self._pipeline_lock:
            inflight = self._pipeline_inflight
        return {"jobs_completed": self.jobs_completed,
                "completions_dropped": self.completions_dropped,
                "polls": int(self._c_polls.value),
                "busy": 1 if self._busy.is_set() else 0,
                "inflight": inflight,
                "pipeline_on": (hasattr(self.backend, "submit")
                                and pipeline_enabled()),
                "pipeline_depth": pipeline_depth()}

    def _collect_gauges(self, reg: "obs.Registry") -> None:
        # Sets the children PRE-CREATED in run() (held on self._gauges)
        # instead of get-or-create per scrape: a scrape racing run()'s
        # cleanup then merely sets detached objects and cannot re-register
        # the just-removed uuid-labeled children.
        g = self._gauges
        if g is None:
            return
        g["in"].set(self._in.depth())
        g["out"].set(self._out.depth())
        g["deferred"].set(len(self._deferred))
        g["busy"].set(1 if self._busy.is_set() else 0)

    # -- compute side ------------------------------------------------------

    def _compute_loop(self) -> None:
        if (hasattr(self.backend, "submit")
                and hasattr(self.backend, "collect")
                and pipeline_enabled()):
            self._compute_loop_pipelined()
        else:
            # The strictly serial path (and every process-only backend):
            # one batch runs decode -> compute -> d2h to completion
            # before the next is touched. DBX_PIPELINE=0 routes two-phase
            # backends here too — the bit-identity reference the
            # pipelined path is verified against.
            self._compute_loop_simple()

    def _take_batch(self):
        """The compute thread's blocking take from the input channel, as
        the ``worker.wait_batch`` span: while it is open the compute side
        is starved, waiting on the control thread for work."""
        with obs.span("worker.wait_batch") as attrs:
            batch = self._in.get()
            attrs["jobs"] = len(batch) if batch is not None else 0
        return batch

    def _compute_loop_simple(self) -> None:
        while True:
            batch = self._take_batch()
            if batch is None:
                return
            # The shared pipeline accounting drives the busy flag here
            # too (one batch in flight at a time on this loop), so every
            # `_busy` mutation stays under the one lock.
            self._pipeline_batch_begin()
            try:
                # Adopt the batch's dispatcher-minted traces: the process
                # span (and everything the backend spans beneath it) joins
                # each job's trace as a child of its dispatch span.
                with obs.trace_context(obs.job_trace_pairs(batch)), \
                        obs.span("worker.process", jobs=len(batch),
                                 worker=self.worker_id):
                    self._hand_off(self.backend.process(batch))
            except Exception as e:
                self._backend_failed(batch, e)
            finally:
                self._pipeline_batch_end()

    def _compute_loop_pipelined(self) -> None:
        """Double-buffered compute pipeline: THIS thread decodes, builds
        page tables, and launches batch N+1 while the collector thread
        blocks on batch N's device drain.

        The reference worker's loop is fully serial — one job finishes
        before the next is touched (reference ``src/worker/process.rs:21-25``);
        SURVEY.md §2.3 (PP row) and §7 hard part (e) prescribe this
        decode -> H2D -> compute overlap instead. Submitted batches hand
        off through a queue whose depth a slot semaphore bounds at
        ``DBX_PIPELINE_DEPTH`` (default 2 — classic double buffering);
        the slot acquire is the backpressure that also stops the control
        thread's polls once the input channel fills behind it. The
        shutdown sentinel flows
        through both stages in order, so every batch taken before it is
        submitted AND collected before the pipeline exits — the
        finish-or-requeue drain contract (whatever a hard kill strands
        is re-queued by lease expiry, never silently lost).
        """
        handoff: queue_mod.Queue = queue_mod.Queue()
        # Depth is enforced by slot reservation BEFORE the submit
        # dispatches device work — bounding the handoff queue instead
        # would let depth+2 submitted batches live on device (the
        # just-submitted one blocked in put, plus the collector's).
        # Depth counts submitted-but-uncollected batches INCLUSIVE of
        # the one being collected: 2 really is one batch on device, one
        # staging — the old opportunistic loop's bound.
        slots = threading.BoundedSemaphore(pipeline_depth())
        self._pipeline_done.clear()
        collector = threading.Thread(target=self._collect_loop,
                                     args=(handoff, slots),
                                     name="dbx-collect", daemon=True)
        collector.start()
        try:
            while True:
                batch = self._take_batch()
                if batch is None:
                    return
                # Backpressure from the collect lane: every slot is held
                # by a submitted batch not yet collected.
                with obs.span("worker.wait_slot", jobs=len(batch)):
                    slots.acquire()
                self._pipeline_batch_begin()
                pending = self._try_submit(batch)
                if pending is None:
                    # Failed submit: the batch is already logged and left
                    # to its lease; nothing enters the pipeline.
                    self._pipeline_batch_end()
                    slots.release()
                    continue
                handoff.put((pending, time.time()))
        finally:
            # Ordered drain: the sentinel lands BEHIND every submitted
            # batch, so the collector finishes them all before exiting —
            # run()'s completion flush then sees the full pipeline.
            handoff.put(None)
            self._pipeline_done.set()
            collector.join()

    def _collect_loop(self, handoff: queue_mod.Queue, slots) -> None:
        """Collector half of the pipeline: drain submitted batches in
        submission order and stream their completions into the out
        channel. Runs on its own thread so the blocking device drain
        (the d2h wait) overlaps the submit thread's host work."""
        while True:
            try:
                # Bounded wait (dbxlint blocking-call: allowlisted
                # pipeline queue wait): the sentinel is the exit
                # protocol; the timeout only guards against a submit
                # thread that died without posting it.
                item = handoff.get(timeout=0.25)
            except queue_mod.Empty:
                if self._pipeline_done.is_set():
                    return
                continue
            if item is None:
                return
            pending, submitted_wall = item
            # The submit-return -> collect-start window: the batch is in
            # flight on the device (jax dispatched eagerly) while the
            # submit thread works on the NEXT batch. Without a span the
            # timeline analyzer would charge this window to transport
            # (uncovered-gap rule); it maps to execute at envelope
            # priority (obs.timeline SPAN_STAGE).
            wait_s = time.time() - submitted_wall
            if wait_s > 0:
                obs.emit_span("worker.inflight", submitted_wall, wait_s,
                              pairs=obs.job_trace_pairs(pending[1]),
                              jobs=len(pending[1]))
            self._collect_into_out(pending)
            self._pipeline_batch_end()
            slots.release()

    def _pipeline_batch_begin(self) -> None:
        with self._pipeline_lock:
            self._pipeline_inflight += 1
            self._busy.set()

    def _pipeline_batch_end(self) -> None:
        with self._pipeline_lock:
            self._pipeline_inflight -= 1
            if self._pipeline_inflight == 0:
                self._busy.clear()

    def _try_submit(self, batch):
        try:
            # The per-batch span chain (worker.submit -> worker.collect ->
            # worker.report): submit covers decode + H2D + kernel launch,
            # collect the device drain + d2h wait, report the completion
            # RPC — the decode->compute->report attribution the JSONL
            # event log reconstructs per batch. The trace context adopts
            # every job's dispatcher-minted (trace_id, dispatch span) pair
            # so the chain stitches cross-process.
            with obs.trace_context(obs.job_trace_pairs(batch)), \
                    obs.span("worker.submit", jobs=len(batch),
                             worker=self.worker_id):
                return (self.backend.submit(batch), batch)
        except Exception as e:
            self._backend_failed(batch, e)
            return None

    def _collect_into_out(self, pending) -> None:
        handle, batch = pending
        try:
            with obs.trace_context(obs.job_trace_pairs(batch)), \
                    obs.span("worker.collect", jobs=len(batch),
                             worker=self.worker_id):
                self._hand_off(self.backend.collect(handle))
        except Exception as e:
            self._backend_failed(batch, e)

    def _hand_off(self, completions) -> None:
        """Encode each completion onto the out channel for the control
        thread: the last step of packing the batch's results, so it
        shares the backend's ``worker.pack`` span name."""
        completions = list(completions)
        with obs.span("worker.pack", jobs=len(completions)):
            for completion in completions:
                self._out.put(completion)

    def _backend_failed(self, batch, e: Exception) -> None:
        """The one handler for a backend exception on any compute path:
        log it, fire the flight trigger and count it. The jobs go back to
        the queue by lease expiry; the count makes the failure loud —
        ``main`` exits non-zero and ``chip_smoke.py`` fails on it."""
        log.exception("backend failed on a %d-job batch; jobs will be "
                      "re-queued by lease expiry", len(batch))
        with self._pipeline_lock:
            self.batches_failed += 1
        self._c_batch_failures.inc()
        obs_flight.trigger("collect_fail", subject=self.worker_id,
                           jobs=len(batch), reason=repr(e))

    # -- control side ------------------------------------------------------

    def run(self, *, max_idle_polls: int | None = None) -> None:
        """Run until stopped (or until ``max_idle_polls`` empty polls).

        ``max_idle_polls`` gives batch-style runs a natural exit: stop after
        that many consecutive empty replies once at least one job was seen.
        """
        channel = grpc.insecure_channel(
            self.target, options=service.default_channel_options())
        stub = service.DispatcherStub(channel)
        if getattr(self.backend, "panel_cache", None) is not None:
            # Compute-thread recovery hook for the evicted-between-poll-
            # and-decode race (gRPC channels are thread-safe); the primary
            # resolution happens in _poll_jobs on this thread.
            self.backend.payload_fetcher = (
                lambda digest: self._fetch_payload(stub, digest))
        if getattr(self.backend, "schedule_registry", None) is not None:
            # Fleet compile-cache exchange rides the jax persistent cache
            # dir this process already configured (a harness's choice is
            # respected); best-effort — None degrades to uncached.
            from .. import tune as tune_mod

            self._compile_sync = tune_mod.attach(registry=self.obs)
        if obs_fleet.telemetry_enabled():
            # Fleet telemetry (round 15): frames ride _poll_jobs; the
            # generation id minted here marks THIS run, so a restarted
            # worker's frames supersede its predecessor's at the
            # dispatcher instead of interleaving with them.
            self._telemetry = obs_fleet.WorkerTelemetry(
                self.worker_id, stats_fn=self._telemetry_stats,
                backend=self.backend, registry=self.obs)
        # The per-worker label set is a deliberate, BOUNDED exception to
        # the obs-cardinality rule: one process hosts a handful of workers
        # and every uuid-labeled child is removed in this method's finally
        # (lifecycle hygiene below), so the series count tracks LIVE
        # workers, not all workers ever seen.
        wid = self.worker_id
        self._gauges = {
            # dbxlint: disable=obs-cardinality -- lifecycle-managed: removed in run()'s finally
            "in": self.obs.gauge("dbx_worker_channel_depth", worker=wid,
                                 channel="in"),
            # dbxlint: disable=obs-cardinality -- lifecycle-managed: removed in run()'s finally
            "out": self.obs.gauge("dbx_worker_channel_depth", worker=wid,
                                  channel="out"),
            # dbxlint: disable=obs-cardinality -- lifecycle-managed: removed in run()'s finally
            "deferred": self.obs.gauge("dbx_worker_deferred_completions",
                                       worker=wid),
            # dbxlint: disable=obs-cardinality -- lifecycle-managed: removed in run()'s finally
            "busy": self.obs.gauge("dbx_worker_busy", worker=wid)}
        self.obs.add_collector(f"worker-{wid}", self._collect_gauges)
        self._compute_thread = threading.Thread(
            target=self._compute_loop, name="dbx-compute", daemon=True)
        self._compute_thread.start()

        idle_polls = 0
        saw_work = False
        next_poll = 0.0
        try:
            while not self._stop.is_set():
                now = time.monotonic()
                if now >= self._next_status:
                    self._next_status = now + self.status_interval_s
                    self._send_status(stub)
                if (now >= self._next_tune_sync
                        and getattr(self.backend, "schedule_registry",
                                    None) is not None):
                    self._next_tune_sync = now + self.tune_sync_interval_s
                    self._sync_tune(stub)
                if now >= next_poll:
                    next_poll = now + self.poll_interval_s
                    got = self._poll_jobs(stub)
                    if got is not None:
                        if got:
                            saw_work = True
                            idle_polls = 0
                        elif (not self._busy.is_set() and self._out.empty()
                                and not self._deferred):
                            idle_polls += 1
                self._drain_completions(stub)
                if (max_idle_polls is not None and saw_work
                        and idle_polls >= max_idle_polls):
                    log.info("idle for %d polls; draining and exiting",
                             idle_polls)
                    break
                time.sleep(min(self.poll_interval_s, 0.05))
            self._shutdown(stub)
        finally:
            if getattr(self.backend, "panel_cache", None) is not None:
                # The fetcher closes over THIS run's channel/stub; a
                # backend outliving the worker loop must not keep (or
                # call) a hook bound to a closed channel.
                self.backend.payload_fetcher = None
            channel.close()
            # Lifecycle hygiene: a long-lived process constructing many
            # Workers (bench's control-plane saturation config) must not
            # accumulate dead collectors or uuid-labeled gauge children —
            # every scrape, GetStats payload, and BENCH obs blob would
            # carry them forever.
            self.obs.remove_collector(f"worker-{self.worker_id}")
            wid = self.worker_id
            for ch in ("in", "out"):
                self.obs.remove_child("dbx_worker_channel_depth",
                                      worker=wid, channel=ch)
            self.obs.remove_child("dbx_worker_deferred_completions",
                                  worker=wid)
            self.obs.remove_child("dbx_worker_busy", worker=wid)

    def stop(self) -> None:
        self._stop.set()

    def _shutdown(self, stub) -> None:
        """Graceful drain: finish queued batches, flush completions.

        The shutdown sentinel traverses the WHOLE pipeline in order —
        input channel, submit stage, handoff queue, collect stage — so
        joining the compute thread here waits for every taken batch to
        be submitted AND collected (``_compute_loop_pipelined``'s
        finally joins its collector); nothing produces into the
        completion queue afterwards and a non-blocking drain is
        exhaustive. A pipeline that cannot finish inside the join budget
        (wedged device) is abandoned with its batches still leased —
        finish-or-requeue, never a silently lost completion. Deferred
        (previously failed) completions get their remaining retry
        attempts inside a bounded exit budget; whatever still fails is
        re-queued by lease expiry dispatcher-side.
        """
        if self._prefetch_q is not None:
            # Best-effort thread: no drain needed, just a clean exit (a
            # straggling warm-up is abandoned with the daemon thread).
            self._prefetch_q.put(None)
            self._prefetch_thread.join(timeout=5.0)
            self._prefetch_q = None
            self._prefetch_thread = None
        self._in.put(None)
        if self._compute_thread is not None:
            self._compute_thread.join(timeout=60.0)
            if self._compute_thread.is_alive():
                log.error("compute pipeline did not drain within the exit "
                          "budget; in-flight batches stay leased and will "
                          "be re-queued by lease expiry")
        deadline = time.monotonic() + 8.0
        self._drain_completions(stub, ignore_status_deadline=True)
        while self._deferred and time.monotonic() < deadline:
            time.sleep(0.1)
            self._drain_completions(stub, ignore_status_deadline=True)
        if self._deferred:
            log.error("exiting with %d undelivered completions "
                      "(leases will re-queue them)", len(self._deferred))

    def _send_status(self, stub) -> None:
        status = (pb.WORKER_STATUS_RUNNING if self._busy.is_set()
                  else pb.WORKER_STATUS_IDLE)
        try:
            with obs.timer(self._h_rpc["SendStatus"]):
                stub.SendStatus(pb.StatusRequest(
                    worker_id=self.worker_id, status=status), timeout=5.0)
            self._log_reconnected()
        except grpc.RpcError as e:
            self._c_rpc_errors["SendStatus"].inc()
            self._log_disconnected(e)

    def _sync_tune(self, stub) -> None:
        """One tuned-schedule / compile-cache sync tick (control thread,
        never sleeps, every leg best-effort — a flaky dispatcher costs a
        tick, never a job):

        - offer cache entries this worker's own compiles just wrote;
        - poll the fleet listing, fetch + install entries we lack (the
          cold-start compile skip);
        - adopt the merged fleet schedule registry from GetStats (the
          push leg rides JobsRequest.schedule_json in `_poll_jobs`).
        """
        sync = self._compile_sync
        try:
            if sync is not None:
                fresh = sync.poll_new()
                if fresh:
                    req = pb.CompiledOffer(
                        worker_id=self.worker_id,
                        entries=[pb.CompiledEntry(key=k, name=n,
                                                  payload=p)
                                 for k, n, p in fresh])
                    try:
                        with obs.timer(self._h_rpc["OfferCompiled"]):
                            stub.OfferCompiled(req, timeout=30.0)
                    except grpc.RpcError as e:
                        # A lost offer must not drop a paid compile wall
                        # from fleet sharing: un-mark so the next poll
                        # re-offers (the remark_dirty twin).
                        self._c_rpc_errors["OfferCompiled"].inc()
                        sync.unmark(fresh)
                        raise _SyncLegFailed from e
                try:
                    with obs.timer(self._h_rpc["FetchCompiled"]):
                        listing = stub.FetchCompiled(pb.CompiledRequest(
                            worker_id=self.worker_id), timeout=10.0)
                    miss = sync.missing(listing.known_keys)
                    # Chunked fetches: one bulk reply for a full store
                    # could exceed the channel's message cap; remaining
                    # keys stay missing and ride the next tick.
                    for i in range(0, len(miss),
                                   self._COMPILE_FETCH_BATCH):
                        chunk = miss[i:i + self._COMPILE_FETCH_BATCH]
                        with obs.timer(self._h_rpc["FetchCompiled"]):
                            got = stub.FetchCompiled(pb.CompiledRequest(
                                worker_id=self.worker_id, keys=chunk),
                                timeout=60.0)
                        installed = sync.install(
                            (e.key, e.name, e.payload)
                            for e in got.entries)
                        sync.count_fleet_misses(len(chunk) - installed)
                except grpc.RpcError as e:
                    self._c_rpc_errors["FetchCompiled"].inc()
                    raise _SyncLegFailed from e
            try:
                with obs.timer(self._h_rpc["GetStats"]):
                    stats = stub.GetStats(pb.StatsRequest(), timeout=10.0)
            except grpc.RpcError as e:
                self._c_rpc_errors["GetStats"].inc()
                raise _SyncLegFailed from e
            if stats.schedule_json:
                self.backend.schedule_registry.merge_json(
                    stats.schedule_json)
            self._log_reconnected()
        except _SyncLegFailed as e:
            self._log_disconnected(e.__cause__)
        except Exception:
            log.exception("tune sync tick failed; will retry next tick")

    def _poll_jobs(self, stub):
        """Request a batch if the compute queue has room; None on RPC error."""
        if self._in.full():
            return None
        self._c_polls.inc()
        schedule_json = ""
        reg = getattr(self.backend, "schedule_registry", None)
        if reg is not None:
            # Gossip-up leg: entries tuned since the last poll (usually
            # empty — zero wire cost on a clean poll).
            schedule_json = reg.take_dirty_json()
        telemetry_json = ""
        if self._telemetry is not None:
            # Fleet telemetry leg: empty when nothing changed inside the
            # heartbeat interval — the same dirty-bit discipline.
            telemetry_json = self._telemetry.take_frame_json()
        req = pb.JobsRequest(
            worker_id=self.worker_id, chips=self.backend.chips,
            jobs_per_chip=self.jobs_per_chip,
            # Digest-only dispatch is safe for ANY backend this worker
            # hosts: backends with a panel cache resolve digests, and
            # payload-less fakes (instant/sleep) never read ohlcv at all.
            accepts_digest_only=True,
            # Spec-batch scenario jobs need a backend that can regenerate
            # panels in-trace; only the JAX backend declares it (and only
            # while the DBX_SCENARIO_FUSED kill switch is up).
            accepts_scenario_batch=bool(
                getattr(self.backend, "accepts_scenario_batch", False)),
            schedule_json=schedule_json,
            telemetry_json=telemetry_json)
        try:
            with obs.timer(self._h_rpc["RequestJobs"], name="worker.poll"):
                reply = stub.RequestJobs(req, timeout=30.0)
            self._log_reconnected()
        except grpc.RpcError as e:
            self._c_rpc_errors["RequestJobs"].inc()
            self._log_disconnected(e)
            if schedule_json and reg is not None:
                # The drained dirty entries never reached the dispatcher:
                # re-mark them so the next successful poll pushes them.
                reg.remark_dirty(schedule_json)
            if telemetry_json and self._telemetry is not None:
                # The frame never arrived: resend on the next poll.
                self._telemetry.remark_dirty()
            return None
        self._c_wire[("RequestJobs", "request")].inc(_pb_size(req))
        self._c_wire[("RequestJobs", "reply")].inc(_pb_size(reply))
        jobs = list(reply.jobs)
        if jobs:
            log.info("received %d jobs", len(jobs))
            self._c_jobs_in.inc(len(jobs))
            self._resolve_payloads(stub, jobs)
            if prefetch_enabled():
                self._prefetch(jobs)
            self._in.put(jobs)
        else:
            self._c_idle_polls.inc()
        return jobs

    def _prefetch(self, jobs) -> None:
        """``DBX_PREFETCH`` (default on): stage a just-received batch's
        inputs on THIS thread while the compute pipeline runs earlier
        batches — the control-loop half of the round-14 stage overlap.

        Two legs, both best-effort and bounded by the batch:

        - **backend warm-up** (``backend.prefetch``, handed to the
          dedicated prefetch thread — page uploads can first-call-
          compile their scatter for seconds, and THIS thread owns the
          SendStatus heartbeat the prune window watches): decode payload
          bytes into the host panel cache and pre-stage device pages, so
          the compute thread's decode becomes a cache hit (the payload
          resolution itself already ran in ``_resolve_payloads`` — the
          PR-5 per-batch fetch memo this leg rides);
        - **compile-cache pull-forward**: first contact with a new
          (strategy, payload-size-bucket) signature pulls the next
          tune-sync tick to NOW, so the FetchCompiled legs (round 10)
          run before the batch's first compile instead of on the 10 s
          timer — a fleet-cached compile stops stalling the compute
          thread for the wall the first worker already paid.
        """
        if getattr(self.backend, "prefetch", None) is not None:
            # Hand the warm-up to the prefetch thread: page uploads and
            # their first-call scatter compiles must not park the
            # heartbeat this thread owns past the dispatcher's prune
            # window.
            if self._prefetch_thread is None:
                self._prefetch_q = queue_mod.Queue()
                self._prefetch_thread = threading.Thread(
                    target=self._prefetch_loop, name="dbx-prefetch",
                    daemon=True)
                self._prefetch_thread.start()
            self._prefetch_q.put(jobs)
        if self._compile_sync is not None:
            fresh = {(j.strategy,
                      (len(j.ohlcv) or j.panel_bytes_len).bit_length())
                     for j in jobs}
            if not fresh <= self._prefetch_seen:
                if len(self._prefetch_seen) > 4096:  # long-lived bound
                    self._prefetch_seen.clear()
                self._prefetch_seen |= fresh
                self._next_tune_sync = 0.0

    def _prefetch_loop(self) -> None:
        """Prefetch thread: best-effort backend warm-ups off the control
        thread. Every warmed path re-resolves through the same caches on
        the compute thread, so racing (or trailing) the batch it staged
        costs nothing but the overlap."""
        while True:
            jobs = self._prefetch_q.get()
            if jobs is None:
                return
            warm = getattr(self.backend, "prefetch", None)
            if warm is None:
                continue
            t0_wall, t0 = time.time(), time.perf_counter()
            try:
                warmed = warm(jobs)
            except Exception:
                log.exception("backend prefetch failed; the compute "
                              "thread will decode inline")
                continue
            if warmed:
                # Prefetched decode IS decode work, done early: the span
                # keeps obs.timeline's decode attribution honest when
                # the compute-side decode span reports a cache hit.
                obs.emit_span("worker.prefetch", t0_wall,
                              time.perf_counter() - t0,
                              pairs=obs.job_trace_pairs(jobs),
                              jobs=len(jobs), warmed=warmed)

    def _resolve_payloads(self, stub, jobs) -> None:
        """Dispatch-by-digest intake: a digest-only job whose panel is not
        already in the backend's cache fetches the bytes by content
        address BEFORE the batch crosses to the compute thread (miss ->
        fetch -> full job). An unfetchable digest leaves the job
        payloadless — the backend then errors the batch loudly and the
        lease requeues it, by which point the dispatcher has forgotten the
        phantom delivery and re-dispatches full bytes. Backends without a
        panel cache (instant/sleep fakes) never decode, so their
        digest-only jobs need no bytes at all."""
        cache = getattr(self.backend, "panel_cache", None)
        if cache is None:
            return
        # Per-batch blob memo: one reply can carry MANY digest-only jobs
        # of one panel (jobs_per_chip > 1 on a shared-panel sweep, where
        # the dispatcher marks the digest delivered at the batch's FIRST
        # job) — the bytes must cross once per batch, not once per job.
        # Seed it with bytes already riding sibling jobs, then fetch each
        # remaining digest at most once.
        blobs: dict[str, bytes] = {}
        for job in jobs:
            if job.panel_digest and job.ohlcv:
                blobs.setdefault(job.panel_digest, job.ohlcv)
            if job.panel_digest2 and job.ohlcv2:
                blobs.setdefault(job.panel_digest2, job.ohlcv2)
        for job in jobs:
            for digest, has_raw, field in (
                    (job.panel_digest, bool(job.ohlcv), "ohlcv"),
                    (job.panel_digest2, bool(job.ohlcv2), "ohlcv2")):
                if not digest or has_raw or cache.contains_series(digest):
                    continue
                if (field == "ohlcv" and job.append_parent_digest
                        and job.append_delta
                        and cache.contains_series(
                            job.append_parent_digest)):
                    # Delta-only append dispatch: the compute path splices
                    # the cached base + append_delta itself; fetching the
                    # full extended panel here would undo the O(ΔT) wire
                    # saving.
                    continue
                blob = blobs.get(digest)
                if blob is None:
                    blob = self._fetch_payload(stub, digest)
                    if blob:
                        blobs[digest] = blob
                if blob:
                    setattr(job, field, blob)

    def _fetch_payload(self, stub, digest: str) -> bytes:
        """One FetchPayload attempt; empty bytes when the dispatcher
        cannot serve the digest (or the RPC fails) — the caller degrades
        to the lease-requeue path, never a failed job."""
        req = pb.PayloadRequest(worker_id=self.worker_id, digest=digest)
        try:
            with obs.timer(self._h_rpc["FetchPayload"]):
                reply = stub.FetchPayload(req, timeout=30.0)
            self._log_reconnected()
        except grpc.RpcError as e:
            self._c_rpc_errors["FetchPayload"].inc()
            self._log_disconnected(e)
            return b""
        self._c_wire[("FetchPayload", "request")].inc(_pb_size(req))
        self._c_wire[("FetchPayload", "reply")].inc(_pb_size(reply))
        if not reply.payload:
            # Not a recovery — don't count it as one (the dispatcher's
            # dbx_payload_fetches_total{outcome="gone"} carries the
            # degraded-period signal).
            log.warning("payload fetch for digest %s came back empty; "
                        "affected jobs will be re-dispatched with full "
                        "bytes", digest[:16])
            return b""
        self._c_fetches.inc()
        return reply.payload

    # Compile-cache entries fetched per FetchCompiled RPC: bounds the
    # reply under the channel message cap even when the fleet store is
    # full (single entries are capped at 64 MB by the store; typical
    # XLA-CPU/TPU entries are KBs).
    _COMPILE_FETCH_BATCH = 32

    # Retry due-times for failed completion RPCs. Attempts are spread over
    # due windows with heartbeats flowing in between — nothing here ever
    # sleeps, so a flaky dispatcher cannot starve liveness.
    _COMPLETION_BACKOFF_S = (0.5, 1.0, 2.0)
    # Completions per CompleteJobs RPC. One unary RPC per completion
    # measured ~2 ms on a loopback Python channel — a ~500 jobs/s control-
    # plane ceiling; batching lifts it an order of magnitude.
    _COMPLETION_BATCH = 256

    def _drain_completions(self, stub, *,
                           ignore_status_deadline: bool = False) -> None:
        """Report queued + due-for-retry completions in batched RPCs.

        Never sleeps, and stops early when a status heartbeat is overdue so
        a slow/flaky dispatcher cannot starve liveness (remaining items are
        picked up on the next loop tick).
        """
        def status_overdue() -> bool:
            return (not ignore_status_deadline
                    and time.monotonic() >= self._next_status)

        now = time.monotonic()
        ready = [(a, c) for due, a, c in self._deferred
                 if due <= now or ignore_status_deadline]
        self._deferred = [d for d in self._deferred
                          if not (d[0] <= now or ignore_status_deadline)]
        while True:
            while len(ready) < self._COMPLETION_BATCH:
                try:
                    ready.append((0, self._out.get_nowait()))
                except queue_mod.Empty:
                    break
            if not ready:
                return
            if status_overdue():
                now = time.monotonic()
                self._deferred.extend((now, a, c) for a, c in ready)
                return
            chunk = ready[:self._COMPLETION_BATCH]
            ready = ready[self._COMPLETION_BATCH:]
            self._report_completions(stub, chunk)

    def _report_completions(self, stub, chunk) -> None:
        """One CompleteJobs attempt for ``chunk`` = [(attempts, completion)];
        on RPC failure each item parks for deferred retry (or is dropped
        once its attempts are exhausted — the lease re-queues the job)."""
        try:
            # Timeout stays under the dispatcher's default 10 s prune window:
            # only ONE batch RPC can delay the next heartbeat (status_overdue
            # yields between chunks), so 8 s bounds the worst heartbeat gap.
            # A link too slow to move a chunk in 8 s fails the attempt; items
            # park for retry and, if attempts exhaust, leases re-queue them.
            # The report span joins each completion's trace (no remote
            # parent — the dispatch span parented the compute chain; the
            # report leg is a root-level stage of the job's timeline). It
            # covers the whole report: the request's build, then the RPC.
            with obs.trace_context([(c.trace_id, "") for _, c in chunk]), \
                    obs.span("worker.report", jobs=len(chunk),
                             worker=self.worker_id):
                req = pb.CompleteBatch(worker_id=self.worker_id, items=[
                    pb.CompleteItem(id=c.job_id, metrics=c.metrics,
                                    elapsed_s=c.elapsed_s,
                                    trace_id=c.trace_id)
                    for _, c in chunk])
                with obs.timer(self._h_rpc["CompleteJobs"]):
                    reply = stub.CompleteJobs(req, timeout=8.0)
            self._log_reconnected()
            self._c_wire[("CompleteJobs", "request")].inc(_pb_size(req))
            self._c_wire[("CompleteJobs", "reply")].inc(_pb_size(reply))
            self.jobs_completed += reply.accepted
            for jid in reply.unknown_ids:
                log.warning("completion %s rejected: unknown job", jid)
        except grpc.RpcError as e:
            self._c_rpc_errors["CompleteJobs"].inc()
            self._log_disconnected(e)
            for attempts, comp in chunk:
                if attempts >= len(self._COMPLETION_BACKOFF_S):
                    self.completions_dropped += 1
                    self._c_dropped.inc()
                    log.error("dropping completion %s after %d attempts "
                              "(lease will re-queue it)", comp.job_id,
                              attempts + 1)
                else:
                    due = (time.monotonic()
                           + self._COMPLETION_BACKOFF_S[attempts])
                    self._deferred.append((due, attempts + 1, comp))

    def _log_disconnected(self, err) -> None:
        if self._connected:
            self._connected = False
            log.error("dispatcher unreachable: %s", getattr(err, "code", err))

    def _log_reconnected(self) -> None:
        if not self._connected:
            self._connected = True
            log.info("dispatcher reachable again")


def make_backend(name: str, **kwargs) -> compute.ComputeBackend:
    if name == "jax":
        return compute.JaxSweepBackend(
            param_chunk=kwargs.get("param_chunk"),
            use_fused=kwargs.get("use_fused"),
            use_mesh=kwargs.get("use_mesh"))
    if name == "instant":
        return compute.InstantBackend()
    if name == "sleep":
        return compute.SleepBackend(kwargs.get("delay_s", 0.05))
    raise ValueError(f"unknown backend {name!r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="dbx worker: poll a dispatcher and run backtest jobs")
    ap.add_argument("--connect", default="localhost:50051")
    ap.add_argument("--id", default=None, help="stable worker id")
    ap.add_argument("--backend", default="jax",
                    choices=("jax", "instant", "sleep"))
    ap.add_argument("--param-chunk", type=int, default=None)
    ap.add_argument("--fused", choices=("auto", "on", "off"), default="auto",
                    help="fused Pallas kernels (auto: on for TPU backends)")
    ap.add_argument("--mesh", choices=("auto", "on", "off"), default="auto",
                    help="shard job groups over the local chip mesh "
                         "(auto: on for multi-chip TPU hosts)")
    ap.add_argument("--poll-s", type=float, default=0.25)
    ap.add_argument("--status-s", type=float, default=1.0)
    ap.add_argument("--jobs-per-chip", type=int, default=1)
    ap.add_argument("--exit-after-idle", type=int, default=None,
                    help="exit after N consecutive empty polls (batch mode)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+ /stats.json) on this "
                         "port (0 = ephemeral; omit to disable)")
    ap.add_argument("--metrics-host", default="0.0.0.0",
                    help="interface for the /metrics server (use 127.0.0.1 "
                         "to scope the scrape surface to this host)")
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    # Runtime lockdep (DBX_LOCKDEP=1): install BEFORE any backend/cache
    # construction so every package lock created below is instrumented.
    from ..analysis import lockdep

    lockdep.maybe_install()
    tristate = {"auto": None, "on": True, "off": False}
    backend = make_backend(args.backend, param_chunk=args.param_chunk,
                           use_fused=tristate[args.fused],
                           use_mesh=tristate[args.mesh])
    worker = Worker(args.connect, backend, worker_id=args.id,
                    poll_interval_s=args.poll_s,
                    status_interval_s=args.status_s,
                    jobs_per_chip=args.jobs_per_chip)
    # SIGTERM/SIGINT -> worker.stop(): run() then drains the compute queue
    # and flushes completions before exiting (_shutdown), so a fleet
    # scale-down loses no finished work (the reference worker had no
    # shutdown path; reference README.md:75-88).
    import signal

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: worker.stop())
    metrics_srv = (obs.MetricsServer(args.metrics_port,
                                     bind=args.metrics_host).start()
                   if args.metrics_port is not None else None)
    log.info("worker %s -> %s (backend=%s, chips=%d)",
             worker.worker_id, args.connect, args.backend, backend.chips)
    try:
        worker.run(max_idle_polls=args.exit_after_idle)
    finally:
        if metrics_srv is not None:
            metrics_srv.stop()
    if worker.batches_failed:
        # A backend that raised is never a clean exit: its jobs only went
        # back to the queue by lease expiry.
        sys.exit(f"worker {worker.worker_id}: {worker.batches_failed} "
                 f"batch(es) failed in the backend")


if __name__ == "__main__":
    main()
