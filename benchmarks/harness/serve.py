"""The system under test as its users run it, and the load on it.

One process: an in-process ``DispatcherServer`` on ``127.0.0.1:0``, one
``Worker`` over the default ``JaxSweepBackend`` (fused, paged and
pipelined as the backend decides), jobs entering through
``JobQueue.enqueue_many``, the dispatcher's intake. A job is complete
when the dispatcher records its result; that instant is the end of the
dispatcher's own ``job`` span, which a span listener collects.
"""

from __future__ import annotations

import threading
import time

import numpy as np


class BackendFailed(RuntimeError):
    """The worker's backend raised on a batch."""


class Service:
    """Dispatcher + one worker, with the completion record the benchmark
    reads. ``keep`` holds job ids whose result blocks are copied as they
    arrive (the dispatcher keeps only its newest results resident)."""

    def __init__(self, config: dict, loop: str, *, backend=None):
        from distributed_backtesting_exploration_tpu import obs
        from distributed_backtesting_exploration_tpu.rpc import compute
        from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
            Dispatcher, DispatcherServer, JobQueue, PeerRegistry)
        from distributed_backtesting_exploration_tpu.rpc.worker import Worker

        w = config["worker"]
        self._obs = obs
        self.queue = JobQueue(lease_s=float(w["lease_s"]))
        self.disp = Dispatcher(
            self.queue, PeerRegistry(prune_window_s=float(w["lease_s"])))
        self.srv = DispatcherServer(self.disp, bind="127.0.0.1:0",
                                    prune_interval_s=5.0).start()
        self.backend = backend or compute.JaxSweepBackend()
        self.worker = Worker(
            f"127.0.0.1:{self.srv.port}", self.backend,
            worker_id="benchmark-worker",
            poll_interval_s=float(w["poll_interval_s"]),
            status_interval_s=float(w["status_interval_s"]),
            jobs_per_chip=int(w["jobs_per_chip"][loop]))
        self.done: dict[str, float] = {}      # job id -> completion (wall)
        self.order: list[str] = []            # completion order
        self.queue_wait: dict[str, float] = {}
        self.keep: set[str] = set()
        self.kept: dict[str, bytes] = {}
        self._scan = 0
        self._retry: list[str] = []
        obs.add_span_listener("benchmark", self._on_span)
        self._thread = threading.Thread(target=self.worker.run,
                                        name="benchmark-worker", daemon=True)
        self._thread.start()

    def _on_span(self, rec: dict) -> None:
        name = rec.get("name")
        if name == "job":
            jid = rec["job"]
            self.done[jid] = rec["t0"] + rec["dur_s"]
            self.order.append(jid)
        elif name == "job.queue_wait":
            self.queue_wait[rec["job"]] = rec["dur_s"]

    def pump(self) -> None:
        """Copy the result blocks of kept jobs that completed since the
        last call; raise if the backend failed."""
        if self.worker.batches_failed:
            raise BackendFailed(f"{self.worker.batches_failed} batch(es) "
                                "failed in the backend")
        new = self.order[self._scan:]
        self._scan += len(new)
        todo = self._retry + [j for j in new if j in self.keep]
        self._retry = []
        for jid in todo:
            blob = self.disp.results.get(jid)
            if blob:
                self.kept[jid] = blob
            else:
                self._retry.append(jid)

    def wait(self, jids, timeout_s: float) -> bool:
        """Wait until every job of ``jids`` completed; False at timeout."""
        deadline = time.time() + timeout_s
        pending = [j for j in jids if j not in self.done]
        while pending:
            self.pump()
            if time.time() > deadline:
                return False
            time.sleep(0.002)
            pending = [j for j in pending if j not in self.done]
        self.pump()
        return True

    def sleep_until(self, t_wall: float) -> None:
        while True:
            self.pump()
            left = t_wall - time.time()
            if left <= 0:
                return
            time.sleep(min(left, 0.01))

    def counters(self) -> dict:
        return self._obs.get_registry().summaries(prefix="dbx_")

    def failures(self) -> int:
        st = self.queue.stats()
        return (int(st["jobs_failed"]) + int(st["jobs_requeued"])
                + int(self.worker.batches_failed))

    def close(self) -> None:
        self._obs.remove_span_listener("benchmark")
        self.worker.stop()
        self._thread.join(timeout=120)
        self.srv.stop()
        if self._thread.is_alive():
            raise RuntimeError("the worker thread did not stop")


def counter_deltas(after: dict, before: dict) -> dict:
    """Counter deltas; a histogram gives its count delta under its key
    and its sum delta under ``<key>:sum``."""
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


class Feeder(threading.Thread):
    """The closed-loop client: keeps at least ``queued`` whole sweeps
    pending in the queue, enqueueing the next sweep whenever fewer wait.
    ``make_sweep(k)`` returns sweep ``k``'s job records."""

    def __init__(self, svc: Service, make_sweep, sweep_jobs: int,
                 queued: int):
        super().__init__(name="benchmark-feeder", daemon=True)
        self.svc = svc
        self.make_sweep = make_sweep
        self.floor = sweep_jobs * queued
        self.sweeps = 0
        self.jobs: list[str] = []
        self.stop_event = threading.Event()

    def run(self) -> None:
        while not self.stop_event.is_set():
            if self.svc.queue.stats()["jobs_pending"] < self.floor:
                recs = self.make_sweep(self.sweeps)
                self.sweeps += 1
                self.jobs.extend(r.id for r in recs)
                self.svc.queue.enqueue_many(recs)
                continue
            time.sleep(0.002)

    def stop(self) -> None:
        self.stop_event.set()
        self.join(timeout=30)


class Arrivals(threading.Thread):
    """The open-loop client: enqueues each job at its due time (wall
    clock), whatever the system is doing; records how late it ran."""

    def __init__(self, svc: Service, due: np.ndarray, records: list):
        super().__init__(name="benchmark-arrivals", daemon=True)
        self.svc = svc
        self.due = due
        self.records = records
        self.lateness = np.zeros(len(records))
        self.stop_event = threading.Event()

    def run(self) -> None:
        i, n = 0, len(self.records)
        while i < n and not self.stop_event.is_set():
            now = time.time()
            if self.due[i] > now:
                time.sleep(min(self.due[i] - now, 0.005))
                continue
            j = i
            while j < n and self.due[j] <= now:
                j += 1
            self.svc.queue.enqueue_many(self.records[i:j])
            self.lateness[i:j] = time.time() - self.due[i:j]
            i = j

    def stop(self) -> None:
        self.stop_event.set()
        self.join(timeout=30)
