"""In-process dispatcher+worker integration over a loopback gRPC channel.

The strategy SURVEY.md §4 prescribes: real server, real worker, fake/instant
compute backend for control-plane tests, and the real JAX backend once for a
numerical end-to-end check against a directly-computed sweep.
"""

import threading
import time

import grpc
import numpy as np
import pytest

from distributed_backtesting_exploration_tpu.rpc import (
    backtesting_pb2 as pb, compute, service, wire)
from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
    Dispatcher, DispatcherServer, JobQueue, PeerRegistry, parse_grid,
    synthetic_jobs)
from distributed_backtesting_exploration_tpu.rpc.worker import Worker


def _server(queue, *, prune_window_s=10.0, prune_interval_s=0.1,
            results_dir=None):
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=prune_window_s),
                      results_dir=results_dir)
    srv = DispatcherServer(disp, bind="localhost:0",
                           prune_interval_s=prune_interval_s).start()
    return disp, srv


_LIVE_WORKERS: list = []


def _run_worker(target, backend, *, max_idle_polls=10, **kw):
    w = Worker(target, backend, poll_interval_s=0.02,
               status_interval_s=0.05, **kw)
    t = threading.Thread(target=lambda: w.run(max_idle_polls=max_idle_polls),
                         daemon=True)
    t.start()
    _LIVE_WORKERS.append((w, t))
    return w, t


@pytest.fixture(autouse=True)
def _stop_workers():
    """Stop every worker thread at test end.

    A leaked polling worker from one test can land on a later test's
    OS-assigned port (reuse) and steal its jobs — observed as a flaky
    metrics mismatch in the golden end-to-end test.
    """
    yield
    while _LIVE_WORKERS:
        w, t = _LIVE_WORKERS.pop()
        w.stop()
        t.join(timeout=10)


GRID = parse_grid("fast=3:5,slow=10:14:2")


def _wait(pred, timeout=20.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def test_end_to_end_instant_backend(tmp_path):
    queue = JobQueue()
    for rec in synthetic_jobs(6, 64, "sma_crossover", GRID):
        queue.enqueue(rec)
    disp, srv = _server(queue, results_dir=str(tmp_path / "results"))
    try:
        backend = compute.InstantBackend()
        w, t = _run_worker(f"localhost:{srv.port}", backend)
        _wait(lambda: queue.drained, msg="queue drained")
        t.join(timeout=10)
        s = queue.stats()
        assert s["jobs_completed"] == 6 and s["jobs_pending"] == 0
        assert not disp.results, "results stay on disk when results_dir set"
        assert w.jobs_completed == 6
        # every result file written
        assert len(list((tmp_path / "results").glob("*.dbxm"))) == 6
    finally:
        srv.stop()


@pytest.mark.parametrize("compression", [grpc.Compression.Gzip, None],
                         ids=["gzip", "plain"])
def test_complete_jobs_blocks_identical_from_gzip_and_plain_channels(
        compression):
    """A client that still gzips its requests (an older worker in a rolling
    upgrade) and one on the plain channel both land byte-identical result
    blocks: the dispatcher decodes each message by its own grpc-encoding."""
    from distributed_backtesting_exploration_tpu.ops.metrics import Metrics

    queue = JobQueue()
    recs = synthetic_jobs(3, 32, "sma_crossover", GRID)
    for rec in recs:
        queue.enqueue(rec)
    disp, srv = _server(queue)
    rng = np.random.default_rng(24)
    channel = grpc.insecure_channel(
        f"localhost:{srv.port}", options=service.default_channel_options(),
        compression=compression)
    try:
        stub = service.DispatcherStub(channel)
        reply = stub.RequestJobs(pb.JobsRequest(
            worker_id="w", chips=1, jobs_per_chip=len(recs)))
        assert len(reply.jobs) == len(recs)
        blocks = {}
        for job in reply.jobs:
            combos = wire.grid_n_combos(job.grid)
            blocks[job.id] = wire.metrics_to_bytes(Metrics(*(
                rng.standard_normal(combos).astype(np.float32)
                for _ in Metrics._fields)))
        crep = stub.CompleteJobs(pb.CompleteBatch(
            worker_id="w",
            items=[pb.CompleteItem(id=jid, metrics=block)
                   for jid, block in blocks.items()]))
    finally:
        channel.close()
        srv.stop()
    assert crep.accepted == len(recs) and not crep.unknown_ids
    assert {jid: bytes(disp.results[jid]) for jid in blocks} == blocks
    assert queue.drained


def test_jax_backend_fused_ragged_batch_matches_direct():
    """A mixed-length job batch stays on the fused path (use_fused=True,
    interpret mode on CPU) and matches per-job direct sweeps — the routing
    must not silently drop ragged fleets to the generic path (VERDICT r2 #6).
    """
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    grid = parse_grid("fast=3:5,slow=10:14:2")
    jobs = (synthetic_jobs(2, 96, "sma_crossover", grid, cost=1e-3, seed=6)
            + synthetic_jobs(2, 150, "sma_crossover", grid, cost=1e-3,
                             seed=7))
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        grid=wire.grid_to_proto(r.grid), cost=r.cost,
                        periods_per_year=252) for r in jobs]
    backend = compute.JaxSweepBackend(use_fused=True)
    completions = backend.process(specs)
    assert len(completions) == len(jobs)
    by_id = {c.job_id: c for c in completions}

    for rec in jobs:
        series = data.from_wire_bytes(rec.ohlcv)
        panel = type(series)(*(jnp.asarray(f)[None, :] for f in series))
        canonical_axes = dict(sorted(rec.grid.items()))
        want = sweep.jit_sweep(
            panel, base.get_strategy("sma_crossover"),
            sweep.product_grid(**canonical_axes), cost=1e-3)
        got = wire.metrics_from_bytes(by_id[rec.id].metrics)
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0], rtol=2e-4, atol=2e-5,
                err_msg=name)


def test_pairs_jobs_over_the_wire_match_direct_sweep():
    """Two-legged pairs jobs travel the full dispatch loop (JobSpec.ohlcv2,
    round 3) and the recorded metrics match a direct run_pairs_sweep — the
    distributed plane covers every strategy family, including BASELINE
    configs[3]."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import pairs
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    grid = {"lookback": np.asarray([8.0, 10.0], np.float32),
            "z_entry": np.asarray([1.0, 2.0], np.float32)}
    queue = JobQueue()
    jobs = synthetic_jobs(3, 96, "pairs", grid, cost=1e-3, seed=9)
    for rec in jobs:
        queue.enqueue(rec)
    disp, srv = _server(queue)
    try:
        w, t = _run_worker(f"localhost:{srv.port}",
                           compute.JaxSweepBackend())
        _wait(lambda: queue.drained, timeout=120.0, msg="queue drained")
        t.join(timeout=10)
    finally:
        srv.stop()
    assert queue.stats()["jobs_completed"] == 3

    for rec in jobs:
        y = data.from_wire_bytes(rec.ohlcv)
        x = data.from_wire_bytes(rec.ohlcv2)
        canonical_axes = dict(sorted(rec.grid.items()))
        want = pairs.run_pairs_sweep(
            jnp.asarray(y.close)[None, :], jnp.asarray(x.close)[None, :],
            sweep.product_grid(**canonical_axes), cost=1e-3)
        got = wire.metrics_from_bytes(disp.results[rec.id])
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0], rtol=2e-4, atol=2e-5,
                err_msg=name)


def test_pairs_jobs_fused_backend_path():
    """use_fused=True routes pairs groups to the Pallas kernel (interpret
    mode on CPU); results match the generic sweep modulo the documented
    knife-edge flip allowance."""
    grid = {"lookback": np.asarray([8.0, 10.0], np.float32),
            "z_entry": np.asarray([1.0, 2.0], np.float32)}
    jobs = synthetic_jobs(2, 96, "pairs", grid, cost=1e-3, seed=11)
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        ohlcv2=r.ohlcv2, grid=wire.grid_to_proto(r.grid),
                        cost=r.cost, periods_per_year=252) for r in jobs]
    fused_out = {c.job_id: wire.metrics_from_bytes(c.metrics)
                 for c in compute.JaxSweepBackend(use_fused=True
                                                  ).process(specs)}
    generic_out = {c.job_id: wire.metrics_from_bytes(c.metrics)
                   for c in compute.JaxSweepBackend(use_fused=False
                                                    ).process(specs)}
    assert set(fused_out) == set(generic_out) == {r.id for r in jobs}
    for jid in fused_out:
        a, b = fused_out[jid], generic_out[jid]
        flipped = np.zeros_like(np.asarray(a.turnover), dtype=bool)
        for name in a._fields:
            av, bv = np.asarray(getattr(a, name)), np.asarray(
                getattr(b, name))
            flipped |= np.abs(av - bv) > (0.01 + 0.01 * np.abs(bv))
        assert flipped.mean() <= 0.05
        for name in a._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(a, name))[~flipped],
                np.asarray(getattr(b, name))[~flipped],
                rtol=2e-3, atol=2e-4, err_msg=name)


def test_pairs_jobs_malformed_complete_empty_not_requeue_loop():
    """A pairs job missing its second leg (or with unequal legs) completes
    with empty metrics and a logged error instead of poisoning co-batched
    jobs or looping through lease requeues forever."""
    from distributed_backtesting_exploration_tpu.utils import data

    grid = {"lookback": np.asarray([8.0], np.float32),
            "z_entry": np.asarray([1.0], np.float32)}
    good = synthetic_jobs(1, 96, "pairs", grid, cost=1e-3, seed=13)[0]
    no_leg = synthetic_jobs(1, 96, "pairs", grid, cost=1e-3, seed=14)[0]
    short = data.synthetic_ohlcv(1, 50, seed=15)
    uneven = synthetic_jobs(1, 96, "pairs", grid, cost=1e-3, seed=16)[0]
    uneven_x = data.to_wire_bytes(type(short)(*(f[0] for f in short)))
    specs = [
        pb.JobSpec(id=good.id, strategy="pairs", ohlcv=good.ohlcv,
                   ohlcv2=good.ohlcv2, grid=wire.grid_to_proto(grid),
                   cost=1e-3, periods_per_year=252),
        pb.JobSpec(id=no_leg.id, strategy="pairs", ohlcv=no_leg.ohlcv,
                   grid=wire.grid_to_proto(grid), cost=1e-3,
                   periods_per_year=252),
        pb.JobSpec(id=uneven.id, strategy="pairs", ohlcv=uneven.ohlcv,
                   ohlcv2=uneven_x, grid=wire.grid_to_proto(grid),
                   cost=1e-3, periods_per_year=252),
    ]
    out = {c.job_id: c for c in compute.JaxSweepBackend().process(specs)}
    assert set(out) == {good.id, no_leg.id, uneven.id}
    assert len(out[good.id].metrics) > 0
    assert out[no_leg.id].metrics == b"" and out[uneven.id].metrics == b""


def test_pairs_job_record_journal_roundtrip(tmp_path):
    """ohlcv2 survives the journal (restart must not lose the second leg)."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobRecord)
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal

    jp = str(tmp_path / "j.jsonl")
    queue = JobQueue(Journal(jp))
    rec = synthetic_jobs(1, 32, "pairs",
                         {"lookback": np.asarray([8.0], np.float32),
                          "z_entry": np.asarray([1.0], np.float32)})[0]
    queue.enqueue(rec)
    q2 = JobQueue()
    assert q2.restore(jp) == 1
    restored = q2.take(1, "w")[0][0]
    assert restored.ohlcv2 == rec.ohlcv2 and restored.ohlcv == rec.ohlcv
    assert isinstance(restored, JobRecord)


class _PipelineProbeBackend:
    """submit/collect backend that records event order and slows collect,
    so the worker's double-buffering is observable: with several batches
    queued, submit(k+1) must precede collected(k) for some k."""

    chips = 1

    def __init__(self, delay_s: float = 0.15):
        self.delay_s = delay_s
        self.events: list = []
        self._lock = threading.Lock()
        self._n = 0

    def submit(self, jobs):
        with self._lock:
            self._n += 1
            n = self._n
        self.events.append(("submit", n))
        return (n, list(jobs))

    def collect(self, handle):
        n, jobs = handle
        time.sleep(self.delay_s)
        self.events.append(("collected", n))
        return [compute.Completion(j.id, b"", self.delay_s) for j in jobs]


def test_pipelined_backend_overlaps_batches():
    """The compute loop must launch batch k+1 while batch k's results are
    still being collected (SURVEY.md §2.3 PP row: decode/H2D/compute
    double-buffering vs the reference's serial loop,
    reference src/worker/process.rs:21-25)."""
    queue = JobQueue()
    for rec in synthetic_jobs(8, 32, "sma_crossover", GRID):
        queue.enqueue(rec)
    disp, srv = _server(queue)
    backend = _PipelineProbeBackend()
    try:
        w, t = _run_worker(f"localhost:{srv.port}", backend, jobs_per_chip=2)
        _wait(lambda: queue.drained, msg="queue drained")
        # drained flips inside the dispatcher's handler, possibly before the
        # worker thread has counted the reply — join before asserting.
        t.join(timeout=10)
    finally:
        srv.stop()
    assert w.jobs_completed == 8
    ev = backend.events
    overlapped = any(
        ev.index(("submit", k + 1)) < ev.index(("collected", k))
        for k in range(1, backend._n)
        if ("submit", k + 1) in ev and ("collected", k) in ev)
    assert overlapped, f"no overlapped batch observed in {ev}"


class _CrashyCollectBackend:
    """submit/collect backend whose collect dies after ``ok_batches``
    collections — a worker whose in-flight pipeline batch is lost
    mid-drain. Those completions never materialize; the LEASE must bring
    the jobs back, never a silent drop."""

    chips = 1

    def __init__(self, ok_batches: int = 2, delay_s: float = 0.08):
        self.ok_batches = ok_batches
        self.delay_s = delay_s
        self._lock = threading.Lock()
        self._n = 0

    def submit(self, jobs):
        return list(jobs)

    def collect(self, jobs):
        time.sleep(self.delay_s)
        with self._lock:
            self._n += 1
            n = self._n
        if n > self.ok_batches:
            raise RuntimeError("simulated mid-batch pipeline death")
        return [compute.Completion(j.id, b"", self.delay_s,
                                   trace_id=j.trace_id) for j in jobs]


def test_pipelined_graceful_stop_zero_lost_completions():
    """Round-14 drain regression: stop a pipelined worker mid-batch with
    batches dying in its collector. Every batch it took must be either
    completed-and-reported (the ordered sentinel drain) or left leased —
    after lease expiry a second worker finishes the remainder and the
    fleet records ZERO lost completions."""
    queue = JobQueue(lease_s=1.5)
    recs = synthetic_jobs(8, 32, "sma_crossover", GRID, seed=812)
    for rec in recs:
        queue.enqueue(rec)
    disp, srv = _server(queue, prune_window_s=30.0)
    backend = _CrashyCollectBackend(ok_batches=2)
    try:
        w, t = _run_worker(f"localhost:{srv.port}", backend,
                           jobs_per_chip=2, max_idle_polls=None)
        _wait(lambda: w.jobs_completed >= 2, msg="first completions")
        w.stop()   # mid-batch: the pipeline still holds taken batches
        t.join(timeout=30)
        assert not t.is_alive(), "graceful stop wedged"
        s = queue.stats()
        # Finish-or-requeue: at stop time every job is accounted for —
        # completed, still pending, or leased awaiting expiry. None gone.
        assert (s["jobs_completed"] + s["jobs_pending"]
                + s["jobs_leased"]) == 8, s
        assert s["jobs_completed"] >= 2
        assert s["jobs_completed"] < 8, \
            "the crashy backend should have stranded some batches"
        # Lease expiry returns the stranded jobs; a healthy worker
        # finishes them.
        w2, t2 = _run_worker(f"localhost:{srv.port}",
                             compute.InstantBackend(), max_idle_polls=50)
        _wait(lambda: queue.drained, timeout=60.0,
              msg="second worker drains the requeued jobs")
        t2.join(timeout=20)
    finally:
        srv.stop()
    s = queue.stats()
    assert s["jobs_completed"] == 8 and s["jobs_pending"] == 0, s
    assert s["jobs_failed"] == 0
    assert s["jobs_requeued"] >= 1, \
        "the stranded batches must have come back through lease expiry"


def test_pipelined_vs_serial_bit_identity_across_routes(monkeypatch):
    """The round-14 acceptance bar: DBX_PIPELINE=1 must not change a
    single result bit vs the serial loop (DBX_PIPELINE=0) on any route —
    dense fused, paged fused, and generic here; the append/carry-hit
    streaming route in its own test below. Completion ORDER may differ;
    bytes per job id may not."""

    def run_route(*, pipeline, use_fused, paged, seed):
        monkeypatch.setenv("DBX_PIPELINE", "1" if pipeline else "0")
        monkeypatch.setenv("DBX_PAGED", "1" if paged else "0")
        recs = (synthetic_jobs(2, 64, "sma_crossover", GRID, cost=1e-3,
                               seed=seed)
                + synthetic_jobs(2, 96, "sma_crossover", GRID, cost=1e-3,
                                 seed=seed + 1))
        # synthetic ids are uuid4 — pin them so the serial and pipelined
        # runs are comparable job-for-job.
        for i, rec in enumerate(recs):
            rec.id = f"bit-{seed}-{i}"
        queue = JobQueue()
        for rec in recs:
            queue.enqueue(rec)
        disp, srv = _server(queue)
        try:
            w, t = _run_worker(f"localhost:{srv.port}",
                               compute.JaxSweepBackend(use_fused=use_fused),
                               jobs_per_chip=2)
            _wait(lambda: queue.drained, timeout=180.0, msg="queue drained")
            w.stop()
            t.join(timeout=20)
        finally:
            srv.stop()
        assert queue.stats()["jobs_failed"] == 0
        assert len(disp.results) == len(recs)
        return {jid: bytes(b) for jid, b in disp.results.items()}

    for route, kw in (
            ("fused", dict(use_fused=True, paged=False, seed=600)),
            ("paged", dict(use_fused=True, paged=True, seed=610)),
            ("generic", dict(use_fused=False, paged=False, seed=620)),
    ):
        serial = run_route(pipeline=False, **kw)
        piped = run_route(pipeline=True, **kw)
        assert set(serial) == set(piped), route
        for jid in serial:
            assert piped[jid] == serial[jid], (route, jid)


def test_pipelined_vs_serial_bit_identity_append_carry_hit(monkeypatch):
    """Bit identity on the streaming route: an append chain served from
    carry checkpoints produces identical bytes under the pipelined and
    serial loops — and the carry HIT actually happened in both (the
    pipeline must not silently degrade appends to full reprices)."""
    import grpc as grpc_mod

    from distributed_backtesting_exploration_tpu.rpc import service

    monkeypatch.setenv("DBX_PAGED", "0")

    def run_chain(*, pipeline, seed):
        monkeypatch.setenv("DBX_PIPELINE", "1" if pipeline else "0")
        full, rec, cut = _stream_setup(seed=seed)
        queue = JobQueue()
        queue.enqueue(rec)
        disp, srv = _server(queue)
        backend = compute.JaxSweepBackend(use_fused=True)
        hit0 = backend._c_append["carry_hit"].value
        channel = grpc_mod.insecure_channel(
            f"localhost:{srv.port}",
            options=service.default_channel_options())
        stub = service.DispatcherStub(channel)
        try:
            w, t = _run_worker(f"localhost:{srv.port}", backend,
                               max_idle_polls=None)
            _wait(lambda: queue.drained, msg="base drained")
            r1 = _append(stub, rec.panel_digest, 128, cut(128, 144))
            assert r1.ok
            _wait(lambda: queue.drained, msg="append 1 drained")
            r2 = _append(stub, r1.panel_digest, 144, cut(144, 160))
            assert r2.ok
            _wait(lambda: queue.drained, msg="append 2 drained")
            w.stop()
            t.join(timeout=20)
        finally:
            channel.close()
            srv.stop()
        assert backend._c_append["carry_hit"].value - hit0 >= 1
        return {"base": bytes(disp.results[rec.id]),
                "r1": bytes(disp.results[r1.job_id]),
                "r2": bytes(disp.results[r2.job_id])}

    serial = run_chain(pipeline=False, seed=77)
    piped = run_chain(pipeline=True, seed=77)
    assert piped == serial


def test_end_to_end_jax_backend_matches_direct_sweep():
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    queue = JobQueue()
    jobs = synthetic_jobs(3, 128, "sma_crossover", GRID, cost=1e-3, seed=5)
    for rec in jobs:
        queue.enqueue(rec)
    disp, srv = _server(queue)
    try:
        w, t = _run_worker(f"localhost:{srv.port}",
                           compute.JaxSweepBackend())
        # Generous timeout: the sweep jit-compiles inside the worker's
        # compute thread, and this box has one CPU core.
        _wait(lambda: queue.drained, timeout=120.0, msg="queue drained")
        t.join(timeout=10)
    finally:
        srv.stop()

    # Direct computation of the same jobs.
    for rec in jobs:
        series = data.from_wire_bytes(rec.ohlcv)
        panel = type(series)(*(jnp.asarray(f)[None, :] for f in series))
        # DBXM param order is canonical: row-major over axes sorted by name
        # (wire.grid_from_proto) — proto map iteration order is unspecified,
        # so decoders must NOT rely on the submitter's dict order.
        canonical_axes = dict(sorted(rec.grid.items()))
        want = sweep.jit_sweep(
            panel, base.get_strategy("sma_crossover"),
            sweep.product_grid(**canonical_axes), cost=1e-3)
        got = wire.metrics_from_bytes(disp.results[rec.id])
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0], rtol=2e-5, atol=2e-6,
                err_msg=name)


def test_dead_worker_jobs_requeued_and_finished_by_second_worker():
    """Fault injection: a worker leases jobs and vanishes; lease expiry +
    peer pruning put them back, and a healthy worker finishes the run."""
    queue = JobQueue(lease_s=0.5)
    for rec in synthetic_jobs(4, 64, "sma_crossover", GRID):
        queue.enqueue(rec)
    disp, srv = _server(queue, prune_window_s=0.5, prune_interval_s=0.05)
    try:
        # Ghost worker: leases 2 jobs via a bare stub, never completes them.
        channel = grpc.insecure_channel(f"localhost:{srv.port}")
        stub = service.DispatcherStub(channel)
        reply = stub.RequestJobs(pb.JobsRequest(
            worker_id="ghost", chips=2, jobs_per_chip=1), timeout=5)
        assert len(reply.jobs) == 2
        channel.close()

        _wait(lambda: queue.stats()["jobs_requeued"] >= 2,
              msg="ghost's leases requeued")
        backend = compute.InstantBackend()
        w, t = _run_worker(f"localhost:{srv.port}", backend)
        _wait(lambda: queue.drained, msg="queue drained by healthy worker")
        assert queue.stats()["jobs_completed"] == 4
        stats = disp.GetStats(pb.StatsRequest(), None)
        assert stats.jobs_completed == 4 and stats.jobs_requeued >= 2
    finally:
        srv.stop()


def test_three_workers_share_queue_without_double_compute():
    """Contention: several live workers race the queue; every job completes
    exactly once (lease discipline + new/dup completion accounting), and
    the per-worker completion counts sum to the job count."""
    queue = JobQueue()
    for rec in synthetic_jobs(30, 32, "sma_crossover", GRID):
        queue.enqueue(rec)
    disp, srv = _server(queue)
    workers = []
    try:
        for i in range(3):
            backend = compute.InstantBackend()
            w, t = _run_worker(f"localhost:{srv.port}", backend,
                               worker_id=f"w{i}", jobs_per_chip=2)
            workers.append((w, t, backend))
        _wait(lambda: queue.drained, msg="queue drained")
        for w, t, _ in workers:
            t.join(timeout=15)
    finally:
        srv.stop()
    s = queue.stats()
    assert s["jobs_completed"] == 30 and s["jobs_failed"] == 0
    total = sum(w.jobs_completed for w, _, _ in workers)
    assert total == 30, f"double-counted completions: {total}"
    # Every job ran exactly once: the backends' seen-lists are disjoint.
    seen = [j for _, _, b in workers for j in b.seen]
    assert len(seen) == len(set(seen)) == 30


def test_worker_survives_dispatcher_restart(tmp_path):
    """The reference panics if the server dies mid-completion; ours retries.

    Run a server, let the worker start polling, stop the server, verify the
    worker thread stays alive through the outage, restart a server on the
    same port with the remaining jobs (journal replay), and finish."""
    jpath = str(tmp_path / "q.jsonl")
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal
    queue = JobQueue(Journal(jpath))
    for rec in synthetic_jobs(2, 64, "sma_crossover", GRID):
        rec.ohlcv, rec.path = rec.ohlcv, None
        queue.enqueue(rec)
    disp, srv = _server(queue)
    port = srv.port
    backend = compute.SleepBackend(0.05)
    w, t = _run_worker(f"localhost:{port}", backend)
    _wait(lambda: queue.stats()["jobs_completed"] >= 1, msg="first completion")
    srv.stop()
    time.sleep(0.3)                      # outage; worker keeps polling
    assert t.is_alive(), "worker must survive a dispatcher outage"

    # Restart on the same port from the journal. Journaled specs carry paths,
    # not inline payloads, so rebuild the pending records with fresh payloads.
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import JobRecord
    queue2 = JobQueue()
    state = Journal.replay(jpath)
    pending = set(state.pending)
    for jid in pending:
        rec = JobRecord.from_journal(state.jobs[jid])
        rec.ohlcv = synthetic_jobs(1, 64, "sma_crossover", GRID)[0].ohlcv
        queue2.enqueue(rec, journal=False)
    disp2 = Dispatcher(queue2, PeerRegistry())
    srv2 = DispatcherServer(disp2, bind=f"localhost:{port}").start()
    try:
        _wait(lambda: queue2.drained, msg="restarted queue drained")
        t.join(timeout=10)
        assert queue2.stats()["jobs_completed"] == len(pending)
    finally:
        srv2.stop()


def test_worker_cli_sigterm_graceful_drain():
    """SIGTERM mid-run: the worker CLI finishes its in-flight batch, flushes
    completions, and exits 0 (the reference worker had no shutdown path —
    its own limitations list, reference README.md:75-88)."""
    import os
    import signal as signal_mod
    import subprocess
    import sys

    queue = JobQueue()
    for rec in synthetic_jobs(6, 32, "sma_crossover", GRID):
        queue.enqueue(rec)
    disp, srv = _server(queue)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "distributed_backtesting_exploration_tpu.rpc.worker",
             "--connect", f"localhost:{srv.port}", "--backend", "sleep",
             "--poll-s", "0.02", "--status-s", "0.1"],
            cwd=repo_root, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        _wait(lambda: queue.stats()["jobs_completed"] >= 1,
              timeout=60.0, msg="first completion before SIGTERM")
        proc.send_signal(signal_mod.SIGTERM)
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, err[-2000:]
    finally:
        srv.stop()
    s = queue.stats()
    # At least the pre-signal completion landed, and nothing was lost in a
    # crash: every non-completed job is either still pending or back on the
    # queue via its lease (not stuck leased to a dead process forever).
    assert s["jobs_completed"] >= 1
    assert s["jobs_completed"] + s["jobs_pending"] + s["jobs_leased"] == 6


def test_empty_queue_returns_empty_reply_not_error():
    queue = JobQueue()
    disp, srv = _server(queue)
    try:
        channel = grpc.insecure_channel(f"localhost:{srv.port}")
        stub = service.DispatcherStub(channel)
        reply = stub.RequestJobs(pb.JobsRequest(
            worker_id="w", chips=1), timeout=5)
        assert len(reply.jobs) == 0      # no gRPC error raised
        stats = stub.GetStats(pb.StatsRequest(), timeout=5)
        assert stats.workers_alive == 1
        channel.close()
    finally:
        srv.stop()


# Wire-contract grid table for every registered strategy (+ the two-legged
# pairs path). Tier-1 runs the four structurally distinct decode shapes
# (test_representative_strategies_travel_the_wire); the full-registry loop
# is its slow twin — each family costs a ~4s generic-path CPU compile and
# the per-kernel fused/generic parity lives elsewhere in tier-1.
_WIRE_GRIDS = {
        "sma_crossover": {"fast": np.float32([3, 5]),
                          "slow": np.float32([13.0])},
        "momentum": {"lookback": np.float32([5, 10])},
        "bollinger": {"window": np.float32([10, 20]),   # two multi-valued
                      "k": np.float32([1.0, 2.0])},     # axes: order matters
        "bollinger_touch": {"window": np.float32([10.0]),
                            "k": np.float32([1.0, 2.0])},
        "donchian": {"window": np.float32([10, 20])},
        "donchian_hl": {"window": np.float32([10, 20])},
        "rsi": {"period": np.float32([7.0]), "band": np.float32([20.0])},
        "stochastic": {"window": np.float32([10.0]),
                       "band": np.float32([25.0])},
        "keltner": {"window": np.float32([12.0]),
                    "k": np.float32([1.5])},
        "macd": {"fast": np.float32([5.0]), "slow": np.float32([13.0]),
                 "signal": np.float32([4.0])},
        "trix": {"span": np.float32([6.0, 9.0]),
                 "signal": np.float32([4.0])},
        "obv_trend": {"window": np.float32([8.0, 15.0])},
        "vwap_reversion": {"window": np.float32([8.0]),
                           "k": np.float32([1.0])},
        "pairs": {"lookback": np.float32([10.0]),
                  "z_entry": np.float32([1.0])},
}


def _assert_strategies_travel_the_wire(grids):
    """Each strategy round-trips through the worker backend (decode, grid
    materialization, routing, metric packing) and matches the direct sweep
    on the same panels — no family is CLI/RPC-only on paper."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base, pairs
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    backend = compute.JaxSweepBackend(use_fused=False)
    for strategy, grid in grids.items():
        recs = synthetic_jobs(2, 128, strategy, grid, cost=1e-3, seed=3)
        specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                            ohlcv2=r.ohlcv2 or b"",
                            grid=wire.grid_to_proto(r.grid), cost=r.cost)
                 for r in recs]
        got = {c.job_id: wire.metrics_from_bytes(c.metrics)
               for c in backend.process(specs)}
        assert set(got) == {r.id for r in recs}, strategy

        # Canonical sorted-axis order — the wire contract's DBXM row order
        # (wire.grid_from_proto) — not dict insertion order.
        flat = sweep.product_grid(
            **{k: jnp.asarray(v) for k, v in sorted(grid.items())})
        if strategy == "pairs":
            ys = [data.from_wire_bytes(s.ohlcv) for s in specs]
            xs = [data.from_wire_bytes(s.ohlcv2) for s in specs]
            want = pairs.run_pairs_sweep(
                jnp.asarray(np.stack([y.close for y in ys])),
                jnp.asarray(np.stack([x.close for x in xs])),
                dict(flat), cost=1e-3)
        else:
            series = [data.from_wire_bytes(s.ohlcv) for s in specs]
            panel = type(series[0])(
                *(jnp.asarray(np.stack([np.asarray(getattr(s, f))
                                        for s in series]))
                  for f in series[0]._fields))
            want = sweep.jit_sweep(panel, base.get_strategy(strategy),
                                   dict(flat), cost=1e-3)
        for i, rec in enumerate(recs):
            for name in want._fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(got[rec.id], name)),
                    np.asarray(getattr(want, name))[i],
                    rtol=2e-4, atol=2e-5,
                    err_msg=f"{strategy}/{name}")


def test_representative_strategies_travel_the_wire():
    """Tier-1 twin of the full-registry loop: the four structurally distinct
    wire shapes — single-field close-only (sma), multi-valued multi-axis
    grid ordering (bollinger), 3-param grid (macd), and the two-legged
    ohlcv2 path (pairs). Also pins the registry against _WIRE_GRIDS so a new
    strategy family can't dodge the slow completeness loop unnoticed."""
    from distributed_backtesting_exploration_tpu.models import base

    # Pairs is the two-legged path (models/pairs.py), not a registry entry.
    assert set(_WIRE_GRIDS) - {"pairs"} == set(base.available_strategies()), (
        "registry changed; extend _WIRE_GRIDS")
    rep = ("sma_crossover", "bollinger", "macd", "pairs")
    _assert_strategies_travel_the_wire({k: _WIRE_GRIDS[k] for k in rep})


@pytest.mark.slow   # ~4s generic-path CPU compile per family, x14 families
def test_every_registered_strategy_travels_the_wire():
    rest = {k: v for k, v in _WIRE_GRIDS.items()
            if k not in ("sma_crossover", "bollinger", "macd", "pairs")}
    _assert_strategies_travel_the_wire(rest)


def test_walkforward_jobs_over_the_wire_match_direct():
    """Walk-forward mode (JobSpec.wf_*): the worker backend's stitched OOS
    metrics row per job must equal the direct walk_forward result; a job
    too short for one train+test window completes with an empty block."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import (
        sweep, walkforward)
    from distributed_backtesting_exploration_tpu.utils import data

    grid = {"fast": np.float32([3, 5]), "slow": np.float32([13.0])}
    recs = synthetic_jobs(3, 200, "sma_crossover", grid, cost=1e-3, seed=7,
                          wf_train=80, wf_test=30, wf_metric="sharpe")
    short = synthetic_jobs(1, 60, "sma_crossover", grid, cost=1e-3, seed=8,
                           wf_train=80, wf_test=30, wf_metric="sharpe")
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        grid=wire.grid_to_proto(r.grid), cost=r.cost,
                        wf_train=r.wf_train, wf_test=r.wf_test,
                        wf_metric=r.wf_metric)
             for r in recs + short]
    got = {c.job_id: c.metrics
           for c in compute.JaxSweepBackend(use_fused=False).process(specs)}
    assert got[short[0].id] == b""   # too short: empty block, still completed

    series = [data.from_wire_bytes(s.ohlcv) for s in specs[:3]]
    panel = type(series[0])(
        *(jnp.asarray(np.stack([np.asarray(getattr(s, f)) for s in series]))
          for f in series[0]._fields))
    flat = sweep.product_grid(
        **{k: jnp.asarray(v) for k, v in sorted(grid.items())})
    want = walkforward.walk_forward(
        panel, base.get_strategy("sma_crossover"), dict(flat), train=80,
        test=30, metric="sharpe", cost=1e-3).oos_metrics
    for i, rec in enumerate(recs):
        m = wire.metrics_from_bytes(got[rec.id])
        for name in m._fields:
            got_v = np.asarray(getattr(m, name))
            assert got_v.shape == (1,), f"{name}: one OOS row expected"
            np.testing.assert_allclose(
                got_v[0], np.asarray(getattr(want, name))[i],
                rtol=2e-4, atol=2e-5, err_msg=name)


def test_walkforward_job_record_journal_roundtrip(tmp_path):
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobRecord)

    rec = JobRecord(id="w1", strategy="sma_crossover",
                    grid={"fast": np.float32([3.0])}, cost=1e-3,
                    ohlcv=b"\x01\x02", wf_train=80, wf_test=30,
                    wf_metric="sortino")
    back = JobRecord.from_journal(rec.journal_form())
    assert (back.wf_train, back.wf_test, back.wf_metric) == (80, 30,
                                                             "sortino")
    plain = JobRecord.from_journal(
        JobRecord(id="p1", strategy="sma_crossover",
                  grid={}, ohlcv=b"\x01").journal_form())
    assert (plain.wf_train, plain.wf_test, plain.wf_metric) == (0, 0, "")


def test_walkforward_unknown_metric_completes_empty():
    """A typo'd wf_metric must complete the jobs with empty blocks (loud
    error), never raise — raising would requeue the group through lease
    expiry forever."""
    grid = {"fast": np.float32([3.0]), "slow": np.float32([13.0])}
    recs = synthetic_jobs(2, 200, "sma_crossover", grid, cost=1e-3, seed=9,
                          wf_train=80, wf_test=30, wf_metric="sharp")
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        grid=wire.grid_to_proto(r.grid), cost=r.cost,
                        wf_train=r.wf_train, wf_test=r.wf_test,
                        wf_metric=r.wf_metric) for r in recs]
    got = {c.job_id: c.metrics
           for c in compute.JaxSweepBackend(use_fused=False).process(specs)}
    assert set(got) == {r.id for r in recs}
    assert all(v == b"" for v in got.values())


def test_chaos_soak_exactly_once(tmp_path):
    """Combined-failure soak: three workers churn a journaled queue while a
    ghost worker abandons leases and the dispatcher restarts mid-run. Every
    job must complete EXACTLY once (the journal's completion record is the
    witness) — none lost, none double-recorded."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobRecord)
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal

    n_jobs = 40
    jpath = str(tmp_path / "q.jsonl")
    queue = JobQueue(Journal(jpath), lease_s=1.0)
    recs = synthetic_jobs(n_jobs, 48, "sma_crossover", GRID, seed=3)
    for rec in recs:
        queue.enqueue(rec)
    disp, srv = _server(queue, prune_window_s=2.0)
    port = srv.port

    # A ghost takes leases and vanishes: expiry must requeue its jobs.
    ghost_taken = queue.take(6, "ghost-worker")
    assert len(ghost_taken) == 6

    # No idle self-exit: a momentarily-empty queue (ghost jobs leased,
    # everything else dispatched) must not let the fleet die pre-crash.
    workers = [_run_worker(f"localhost:{port}", compute.InstantBackend(),
                           max_idle_polls=None)
               for _ in range(3)]
    _wait(lambda: queue.stats()["jobs_completed"] >= n_jobs // 3,
          timeout=60.0, msg="first third completed")

    # Dispatcher crash + restart on the same port, state from the journal.
    srv.stop()
    time.sleep(0.3)
    assert all(t.is_alive() for _, t in workers)
    state = Journal.replay(jpath)
    queue2 = JobQueue(lease_s=1.0)
    for jid in state.pending:
        # Inline payloads are journaled (ohlcv_b64), so from_journal
        # restores a fully dispatchable record.
        queue2.enqueue(JobRecord.from_journal(state.jobs[jid]),
                       journal=False)
    already = len(state.completed)
    disp2 = Dispatcher(queue2, PeerRegistry(prune_window_s=2.0))
    srv2 = DispatcherServer(disp2, bind=f"localhost:{port}",
                            prune_interval_s=0.1).start()
    try:
        _wait(lambda: queue2.drained, timeout=120.0,
              msg="post-restart queue drained")
        for w, t in workers:
            w.stop()
        for w, t in workers:
            t.join(timeout=10)
    finally:
        srv2.stop()

    # Exactly once: pre-crash completions + post-crash completions cover
    # every job id with no overlap and no loss.
    assert already + queue2.stats()["jobs_completed"] == n_jobs
    post = queue2.completed_ids()
    assert set(state.completed).isdisjoint(post)
    assert set(state.completed) | post == {r.id for r in recs}


def test_topk_jobs_over_the_wire_match_direct_sweep(tmp_path):
    """JobSpec.top_k: workers reduce on-device and ship DBXS blocks whose
    rows are the direct sweep's top-k by the rank metric (the reduce-on-
    chip, move-scalars-over-DCN mode)."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.utils import data

    grid = parse_grid("fast=3:6,slow=10:16:2")   # P = 9 combos
    k = 4
    queue = JobQueue()
    recs = synthetic_jobs(4, 96, "sma_crossover", grid, cost=1e-3, seed=3,
                          top_k=k, rank_metric="sharpe")
    for rec in recs:
        queue.enqueue(rec)
    results = tmp_path / "results"
    disp, srv = _server(queue, results_dir=str(results))
    try:
        w, t = _run_worker(f"localhost:{srv.port}",
                           compute.JaxSweepBackend(use_fused=False))
        _wait(lambda: queue.drained, msg="queue drained")
    finally:
        srv.stop()

    canonical_axes = sweep.product_grid(**dict(sorted(recs[0].grid.items())))
    for rec in recs:
        blob = (results / f"{rec.id}.dbxm").read_bytes()
        assert wire.result_kind(blob) == "topk"
        idx, got, metric = wire.topk_from_bytes(blob)
        assert metric == "sharpe" and idx.shape == (k,)

        series = data.from_wire_bytes(rec.ohlcv)
        panel = type(series)(*(jnp.asarray(f)[None, :] for f in series))
        want = sweep.jit_sweep(panel, base.get_strategy("sma_crossover"),
                               canonical_axes, cost=1e-3)
        sharpe = np.asarray(want.sharpe)[0]
        order = np.argsort(-sharpe, kind="stable")[:k]
        np.testing.assert_array_equal(np.sort(idx), np.sort(order))
        # Rows are best-first and carry the full metric tuple at idx.
        np.testing.assert_allclose(np.asarray(got.sharpe),
                                   sharpe[idx], rtol=1e-5, atol=1e-6)
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0][idx],
                rtol=2e-4, atol=2e-5, err_msg=name)


def test_topk_unknown_rank_metric_completes_empty(tmp_path):
    """A top-k request naming an unknown metric is validated-bad: the jobs
    complete with EMPTY payloads (no requeue loop, no result files)."""
    queue = JobQueue()
    recs = synthetic_jobs(2, 64, "sma_crossover", GRID, top_k=3,
                          rank_metric="not_a_metric")
    for rec in recs:
        queue.enqueue(rec)
    results = tmp_path / "results"
    disp, srv = _server(queue, results_dir=str(results))
    try:
        w, t = _run_worker(f"localhost:{srv.port}",
                           compute.JaxSweepBackend(use_fused=False))
        _wait(lambda: queue.drained, msg="queue drained")
        s = queue.stats()
        assert s["jobs_completed"] == 2
        assert not list(results.glob("*.dbxm"))
    finally:
        srv.stop()


def test_topk_fused_and_pairs_paths_match_generic():
    """top_k composes with the fused routing and the two-legged pairs path
    (backend-level, no server): each completion is a DBXS block matching
    the corresponding full sweep's top-k rows."""
    from distributed_backtesting_exploration_tpu.parallel import sweep

    k = 3
    grid = parse_grid("fast=3:5,slow=10:14:2")
    sma = synthetic_jobs(2, 96, "sma_crossover", grid, cost=1e-3, seed=6,
                         top_k=k, rank_metric="total_return")
    pgrid = parse_grid("lookback=6;10,z_entry=0.5;1.0;1.5")
    prs = synthetic_jobs(2, 96, "pairs", pgrid, cost=1e-3, seed=7,
                         top_k=k, rank_metric="sharpe")
    recs = sma + prs
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        ohlcv2=r.ohlcv2 or b"",
                        grid=wire.grid_to_proto(r.grid), cost=r.cost,
                        periods_per_year=252, top_k=r.top_k,
                        rank_metric=r.rank_metric) for r in recs]
    fused_backend = compute.JaxSweepBackend(use_fused=True)
    got = {c.job_id: c.metrics for c in fused_backend.process(specs)}

    full_specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                             ohlcv2=r.ohlcv2 or b"",
                             grid=wire.grid_to_proto(r.grid), cost=r.cost,
                             periods_per_year=252) for r in recs]
    full = {c.job_id: wire.metrics_from_bytes(c.metrics)
            for c in compute.JaxSweepBackend(use_fused=True)
            .process(full_specs)}

    from distributed_backtesting_exploration_tpu.ops.metrics import (
        metric_sign)

    for rec in recs:
        idx, m, metric = wire.topk_from_bytes(got[rec.id])
        assert metric == rec.rank_metric
        ref = np.asarray(getattr(full[rec.id], metric))
        order = np.argsort(-metric_sign(metric) * ref, kind="stable")[:k]
        np.testing.assert_array_equal(np.sort(idx), np.sort(order))
        np.testing.assert_allclose(np.asarray(getattr(m, metric)),
                                   ref[idx], rtol=1e-5, atol=1e-6)


def test_pairs_walkforward_jobs_over_the_wire_match_direct():
    """Walk-forward pairs jobs (JobSpec.wf_* + two legs): the worker's
    stitched OOS row per job equals walk_forward_pairs directly; a job too
    short for one train+test window completes with an empty block."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.parallel import (
        sweep, walkforward)
    from distributed_backtesting_exploration_tpu.utils import data

    grid = parse_grid("lookback=8;12,z_entry=0.8;1.5")
    recs = synthetic_jobs(3, 240, "pairs", grid, cost=1e-3, seed=21,
                          wf_train=120, wf_test=40, wf_metric="sharpe")
    short = synthetic_jobs(1, 60, "pairs", grid, cost=1e-3, seed=22,
                           wf_train=120, wf_test=40, wf_metric="sharpe")
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        ohlcv2=r.ohlcv2, grid=wire.grid_to_proto(r.grid),
                        cost=r.cost, wf_train=r.wf_train, wf_test=r.wf_test,
                        wf_metric=r.wf_metric)
             for r in recs + short]
    got = {c.job_id: c.metrics
           for c in compute.JaxSweepBackend(use_fused=False).process(specs)}
    assert got[short[0].id] == b""   # too short: empty block, completed

    ys = [data.from_wire_bytes(r.ohlcv) for r in recs]
    xs = [data.from_wire_bytes(r.ohlcv2) for r in recs]
    y = jnp.asarray(np.stack([np.asarray(s.close) for s in ys]))
    x = jnp.asarray(np.stack([np.asarray(s.close) for s in xs]))
    flat = sweep.product_grid(
        **{k: jnp.asarray(v) for k, v in sorted(grid.items())})
    want = walkforward.walk_forward_pairs(
        y, x, dict(flat), train=120, test=40, metric="sharpe",
        cost=1e-3).oos_metrics
    for i, rec in enumerate(recs):
        m = wire.metrics_from_bytes(got[rec.id])
        for name in m._fields:
            got_v = np.asarray(getattr(m, name))
            assert got_v.shape == (1,), f"{name}: one OOS row expected"
            np.testing.assert_allclose(
                got_v[0], np.asarray(getattr(want, name))[i],
                rtol=2e-4, atol=2e-5, err_msg=name)


def test_best_returns_jobs_over_the_wire_match_direct_composition(tmp_path):
    """JobSpec.best_returns end to end over real gRPC: workers ship DBXP
    blocks (best combo + net-return series) and `aggregate --portfolio`
    composes them into the book the direct library composition produces."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import (
        portfolio as portfolio_mod, sweep)
    from distributed_backtesting_exploration_tpu.rpc import aggregate
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal
    from distributed_backtesting_exploration_tpu.utils import data

    journal_path = str(tmp_path / "journal.jsonl")
    results = tmp_path / "results"
    queue = JobQueue(Journal(journal_path))
    grid = parse_grid("fast=3:6,slow=10:16:2")
    recs = synthetic_jobs(4, 96, "sma_crossover", grid, cost=1e-3, seed=9,
                          best_returns=True, rank_metric="sharpe")
    for rec in recs:
        queue.enqueue(rec)
    disp, srv = _server(queue, results_dir=str(results))
    try:
        _run_worker(f"localhost:{srv.port}",
                    compute.JaxSweepBackend(use_fused=False))
        _wait(lambda: queue.drained, msg="queue drained")
    finally:
        srv.stop()

    for rec in recs:
        blob = (results / f"{rec.id}.dbxm").read_bytes()
        assert wire.result_kind(blob) == "returns"
        _, _, ret, metric = wire.best_returns_from_bytes(blob)
        assert metric == "sharpe" and ret.shape == (96,)

    out = aggregate.portfolio(str(results), journal_path, weights="equal")
    assert out["legs_composed"] == 4 and out["bars"] == 96

    series = [data.from_wire_bytes(rec.ohlcv) for rec in recs]
    panel = type(series[0])(*(jnp.stack([np.asarray(getattr(s, f))
                                         for s in series])
                              for f in series[0]._fields))
    canonical = sweep.product_grid(**dict(sorted(recs[0].grid.items())))
    pm, _ = portfolio_mod.sweep_and_compose(
        panel, base.get_strategy("sma_crossover"), canonical, cost=1e-3)
    assert out["portfolio"]["sharpe"] == pytest.approx(
        float(pm.sharpe), rel=2e-4, abs=2e-5)


def test_obs_end_to_end_metrics_and_extended_stats(tmp_path):
    """The observability acceptance path: a dispatcher+worker run exports
    non-empty RPC latency histograms, queue-depth gauges, and worker
    per-batch span timings via BOTH /metrics (Prometheus text) and the
    extended GetStats obs_json payload."""
    import json
    import urllib.request

    from distributed_backtesting_exploration_tpu import obs
    from distributed_backtesting_exploration_tpu.obs import dump

    # Fresh registry: assertions must not depend on what earlier tests
    # recorded into the process-global one. The worker's span chain and
    # the compute backend record globally, so only dispatcher/worker
    # families use the injected registry.
    reg = obs.Registry()
    queue = JobQueue()
    for rec in synthetic_jobs(8, 64, "sma_crossover", GRID):
        queue.enqueue(rec)
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=10.0),
                      results_dir=str(tmp_path / "results"), registry=reg)
    srv = DispatcherServer(disp, bind="localhost:0", prune_interval_s=0.1,
                           metrics_port=0).start()
    try:
        backend = compute.InstantBackend()
        w = Worker(f"localhost:{srv.port}", backend, poll_interval_s=0.02,
                   status_interval_s=0.05, registry=reg)
        t = threading.Thread(target=lambda: w.run(max_idle_polls=10),
                             daemon=True)
        t.start()
        _LIVE_WORKERS.append((w, t))
        _wait(lambda: queue.drained, msg="queue drained")

        # -- extended stats over the existing wire --------------------------
        import grpc as grpc_mod

        from distributed_backtesting_exploration_tpu.rpc import (
            backtesting_pb2 as pb2, service as service_mod)

        channel = grpc_mod.insecure_channel(f"localhost:{srv.port}")
        try:
            stub = service_mod.DispatcherStub(channel)
            reply = stub.GetStats(pb2.StatsRequest(), timeout=10.0)
            assert reply.jobs_completed == 8
            ext = json.loads(reply.obs_json)
        finally:
            channel.close()
        assert ext["dbx_rpc_seconds{method=RequestJobs}"]["count"] > 0
        assert ext["dbx_rpc_seconds{method=CompleteJobs}"]["count"] > 0
        assert ext["dbx_rpc_seconds{method=RequestJobs}"]["sum"] > 0
        assert ext["dbx_queue_jobs{pool=completed}"] == 8.0
        assert ext["dbx_queue_jobs{pool=pending}"] == 0.0
        assert ext["dbx_jobs_dispatched_total"] == 8.0
        assert ext["dbx_completions_total{outcome=new}"] == 8.0

        # -- /metrics (Prometheus text) -------------------------------------
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.metrics.port}/metrics",
            timeout=10).read().decode()
        assert 'dbx_rpc_seconds_count{method="RequestJobs"}' in body
        assert 'dbx_rpc_seconds_bucket{method="RequestJobs",le="+Inf"}' \
            in body
        assert 'dbx_queue_jobs{pool="completed"} 8.0' in body
        # worker-side client RPC latency + per-batch span chain
        assert 'dbx_worker_rpc_seconds_count{method="CompleteJobs"}' in body
        ws = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.metrics.port}/stats.json",
            timeout=10).read())
        spans = obs.get_registry().summaries(prefix="dbx_span")
        assert spans["dbx_span_seconds{span=worker.process}"]["count"] > 0
        assert ws["dbx_worker_rpc_seconds"]["type"] == "histogram"

        # -- dump CLI smoke against the live endpoint -----------------------
        assert dump.main([f"http://127.0.0.1:{srv.metrics.port}"]) == 0
    finally:
        srv.stop()


def test_obs_pipelined_span_chain_and_kernel_attribution(tmp_path):
    """The JAX backend populates the decode -> submit -> collect span chain,
    per-route kernel wall-time, and the JSONL event log — and (round 7)
    every span joins the dispatcher-minted trace: one merged trace per
    job whose reconstructed timeline covers the whole lifecycle with
    critical-path stage attribution summing to the measured end-to-end
    wall (the acceptance contract)."""
    import json

    from distributed_backtesting_exploration_tpu import obs
    from distributed_backtesting_exploration_tpu.obs import (
        events, timeline)

    jsonl = str(tmp_path / "events.jsonl")
    events.configure(jsonl)
    try:
        queue = JobQueue()
        jobs = synthetic_jobs(3, 64, "sma_crossover", GRID)
        for rec in jobs:
            queue.enqueue(rec)
        # Minted at enqueue: every record carries a distinct trace id.
        assert all(rec.trace_id for rec in jobs)
        assert len({rec.trace_id for rec in jobs}) == 3
        disp, srv = _server(queue, results_dir=str(tmp_path / "results"))
        try:
            _run_worker(f"localhost:{srv.port}",
                        compute.JaxSweepBackend(use_fused=True))
            _wait(lambda: queue.drained, msg="queue drained")
        finally:
            srv.stop()
    finally:
        events.configure(None)

    s = obs.get_registry().summaries()
    assert s["dbx_span_seconds{span=worker.submit}"]["count"] > 0
    assert s["dbx_span_seconds{span=worker.collect}"]["count"] > 0
    assert s["dbx_span_seconds{span=worker.report}"]["count"] > 0
    assert s["dbx_compute_decode_seconds"]["count"] > 0
    assert s["dbx_compute_decode_bytes_total"] > 0
    # The collect lane, split per group: device wait, copy back, packing.
    for phase in ("device_wait", "d2h", "pack"):
        assert s[f"dbx_span_seconds{{span=worker.{phase}}}"]["count"] > 0
    assert s["dbx_compute_d2h_bytes_total"] > 0
    # per-strategy kernel wall keyed by route:strategy, compile/execute split
    kern = [k for k in s if k.startswith("dbx_kernel_submit_seconds")
            and "fused:sma_crossover" in k]
    assert kern, sorted(k for k in s if k.startswith("dbx_kernel"))
    assert any("phase=compile" in k for k in kern)
    # combos credited: 3 jobs x |GRID| combos
    import numpy as np

    combos = int(np.prod([v.size for v in GRID.values()]))
    assert s["dbx_backtests_total"] >= 3 * combos
    # event log carries the span chain for post-mortem reconstruction
    names = {json.loads(ln)["name"] for ln in open(jsonl)
             if json.loads(ln).get("ev") == "span"}
    assert {"worker.submit", "worker.collect", "worker.report"} <= names

    # -- distributed-trace stitching (the tentpole acceptance) --------------
    evs, malformed = timeline.parse_events([jsonl])
    assert malformed == 0
    timelines = timeline.reconstruct(evs)
    by_trace = {rec.trace_id: rec for rec in jobs}
    assert set(timelines) == set(by_trace)
    all_stages_seen = set()
    per_job_stages = {}
    for tid, tl in timelines.items():
        assert tl.job_id == by_trace[tid].id
        span_names = {sp["name"] for sp in tl.spans}
        # Dispatcher- and worker-side spans share ONE trace id.
        assert {"job.queue_wait", "job.dispatch", "job",
                "worker.submit", "worker.report"} <= span_names
        # Worker-side chain parents onto the dispatcher's dispatch span.
        dispatch_sid = next(sp["span_id"] for sp in tl.spans
                            if sp["name"] == "job.dispatch")
        submit = next(sp for sp in tl.spans
                      if sp["name"] == "worker.submit")
        assert submit["parent_id"] == dispatch_sid
        # Critical-path stage attribution sums to the measured e2e wall
        # (within the acceptance's 10% slack; equality by construction,
        # the slack absorbs clock jitter only).
        stages = timeline.critical_path(tl)
        assert tl.e2e_dur > 0
        assert sum(stages.values()) == pytest.approx(tl.e2e_dur, rel=0.10)
        assert stages["queue_wait"] > 0 and stages["dispatch"] > 0
        per_job_stages[tid] = {k for k, v in stages.items() if v > 0}
        all_stages_seen |= per_job_stages[tid]
    # Across the batch every lifecycle stage appears (compile lands on the
    # cold-jit job, execute on the warm ones; jobs_per_chip=1 dispatches
    # them as separate single-job batches)...
    assert {"queue_wait", "dispatch", "decode", "compile", "execute",
            "d2h", "report"} <= all_stages_seen
    # ...and the cold job's SINGLE timeline contains the full lifecycle
    # (the acceptance contract: one job, one merged trace, every stage).
    assert any({"queue_wait", "dispatch", "decode", "compile", "execute",
                "d2h", "report"} <= st for st in per_job_stages.values()), \
        per_job_stages

    # The obs_json wire surface ships the same spans (bounded ring tail).
    ext = json.loads(disp.GetStats(pb.StatsRequest(), None).obs_json)
    ring_names = {r["name"] for r in ext["dbx_spans_recent"]}
    assert {"job", "job.dispatch"} <= ring_names

    # CLI smoke over the real log: text + json, --job filter.
    assert timeline.main(["--jsonl", jsonl, "--format", "json",
                          "--job", jobs[0].id]) == 0


def test_fused_batch_collect_lane_spans():
    """One fused batch through an in-process worker: the compute thread's
    wait for the batch, then — inside ``worker.collect`` — the device
    wait, the copy back and the packing, each a span of the whole
    batch (``jobs``), never one per job."""
    from distributed_backtesting_exploration_tpu import obs

    obs.configure_ring(4096)
    queue = JobQueue()
    jobs = synthetic_jobs(4, 64, "sma_crossover", GRID)
    for rec in jobs:
        queue.enqueue(rec)
    disp, srv = _server(queue)
    try:
        w, t = _run_worker(f"localhost:{srv.port}",
                           compute.JaxSweepBackend(use_fused=True),
                           jobs_per_chip=4)
        _wait(lambda: queue.drained, msg="queue drained")
        t.join(timeout=10)
    finally:
        srv.stop()
        ring = obs.recent_spans()
        obs.configure_ring()
    assert w.jobs_completed == 4 and w.batches_failed == 0
    # The batch's collect span, and the lane's spans that carry its trace.
    collect = [r for r in ring if r["name"] == "worker.collect"
               and r.get("jobs") == 4]
    assert len(collect) == 1
    col = collect[0]
    inside = {r["name"]: r for r in ring
              if r.get("parent_id") == col["span_id"]}
    assert {"worker.device_wait", "worker.d2h", "worker.pack"} <= set(inside)
    for name in ("worker.device_wait", "worker.d2h", "worker.pack"):
        r = inside[name]
        assert r["parent"] == "worker.collect" and r["jobs"] == 4
        assert col["t0"] <= r["t0"]
        assert r["t0"] + r["dur_s"] <= col["t0"] + col["dur_s"] + 1e-3
    assert "cache_hit" in inside["worker.d2h"]
    lane = sum(inside[n]["dur_s"]
               for n in ("worker.device_wait", "worker.d2h"))
    assert lane <= col["dur_s"]
    # The wait that took this batch off the input channel.
    waits = [r for r in ring if r["name"] == "worker.wait_batch"]
    assert any(r.get("jobs") == 4 for r in waits)
    assert all("trace_id" not in r and "traces" not in r for r in waits)


@pytest.mark.slow   # subprocess worker + real cross-process log merge
def test_trace_stitching_across_processes(tmp_path):
    """Multi-process twin of the stitching test: the dispatcher logs to
    one JSONL in this process while a worker CLI SUBPROCESS (DBX_OBS_JSONL
    env opt-in) logs to another; obs.timeline merges the two files into
    one trace per job with both processes' spans."""
    import json as json_mod
    import os
    import subprocess
    import sys

    from distributed_backtesting_exploration_tpu.obs import (
        events, timeline)

    disp_log = str(tmp_path / "dispatcher.jsonl")
    work_log = str(tmp_path / "worker.jsonl")
    events.configure(disp_log)
    try:
        queue = JobQueue()
        jobs = synthetic_jobs(4, 32, "sma_crossover", GRID)
        for rec in jobs:
            queue.enqueue(rec)
        disp, srv = _server(queue)
        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m",
                 "distributed_backtesting_exploration_tpu.rpc.worker",
                 "--connect", f"localhost:{srv.port}", "--backend", "sleep",
                 "--poll-s", "0.02", "--status-s", "0.1",
                 "--exit-after-idle", "10"],
                cwd=repo_root, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
                env={**os.environ, "DBX_OBS_JSONL": work_log,
                     "JAX_PLATFORMS": "cpu"})
            out, err = proc.communicate(timeout=120)
            assert proc.returncode == 0, err[-2000:]
            assert queue.stats()["jobs_completed"] == 4
        finally:
            srv.stop()
    finally:
        events.configure(None)

    evs, _ = timeline.parse_events([disp_log, work_log])
    pids = {r.get("pid") for r in evs}
    assert len(pids) == 2, "expected spans from two processes"
    timelines = timeline.reconstruct(evs)
    assert set(timelines) == {rec.trace_id for rec in jobs}
    for tl in timelines.values():
        names = {sp["name"] for sp in tl.spans}
        assert {"job.queue_wait", "job.dispatch", "job"} <= names
        assert {"worker.process", "worker.report"} & names
        assert len({sp["pid"] for sp in tl.spans}) == 2
        stages = timeline.critical_path(tl)
        assert sum(stages.values()) == pytest.approx(tl.e2e_dur, rel=0.10)
        assert stages["execute"] > 0 and stages["report"] >= 0


def _shared_panel_jobs(n, n_bars=96, seed=11, grid=None):
    """N sma jobs all carrying the SAME panel bytes — the multi-job-per-
    panel workload dispatch-by-digest exists for."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobRecord)
    from distributed_backtesting_exploration_tpu.utils import data

    series = data.synthetic_ohlcv(1, n_bars, seed=seed)
    one = type(series)(*(np.asarray(f[0]) for f in series))
    blob = data.to_wire_bytes(one)
    return one, [JobRecord(id=f"dig-{seed}-{i}", strategy="sma_crossover",
                           grid=grid or GRID, cost=1e-3, ohlcv=blob)
                 for i in range(n)]


def test_dispatch_by_digest_cache_hits_and_matching_results(tmp_path,
                                                            monkeypatch):
    """The dispatch-by-digest tentpole end to end: jobs sharing ONE panel
    ship the bytes once (every later delivery is digest-only), the
    worker's two-level cache serves the repeats — decode AND h2d skipped,
    asserted via the spans' cache_hit attrs — and the stored results
    still match the direct sweep.

    Pinned to the DENSE path (DBX_PAGED=0): with round-10 paging live,
    fused groups serve from the page pool and never touch the device
    block level this test asserts — the paged twin of this flow (pool
    hits, no re-upload on warm re-submit) lives in tests/test_paged.py,
    and the kill switch gets its integration coverage here."""
    import jax.numpy as jnp

    from distributed_backtesting_exploration_tpu import obs
    from distributed_backtesting_exploration_tpu.models import base
    from distributed_backtesting_exploration_tpu.parallel import sweep

    monkeypatch.setenv("DBX_PAGED", "0")
    one, recs = _shared_panel_jobs(4)
    queue = JobQueue()
    for rec in recs:
        queue.enqueue(rec)
    # Content-addressed: four jobs, ONE stored panel.
    assert queue.panel_store.stats()["panels"] == 1
    assert len({r.panel_digest for r in recs}) == 1

    disp, srv = _server(queue, results_dir=str(tmp_path / "results"))
    backend = compute.JaxSweepBackend(use_fused=True)
    digest_only0 = disp._c_payloads["digest_only"].value
    saved0 = disp._c_bytes_saved.value
    host_hits0 = backend.panel_cache._c_hits["host"].value
    dev_hits0 = backend.panel_cache._c_hits["device"].value
    try:
        # jobs_per_chip=1 -> one job per poll, so deliveries 2..4 are
        # digest-only and hit the cache delivery 1 primed.
        w, t = _run_worker(f"localhost:{srv.port}", backend,
                           jobs_per_chip=1)
        _wait(lambda: queue.drained, msg="queue drained")
        t.join(timeout=10)
    finally:
        srv.stop()
    assert w.jobs_completed == 4
    assert queue.stats()["jobs_failed"] == 0
    assert disp._c_payloads["digest_only"].value - digest_only0 >= 3
    assert disp._c_bytes_saved.value - saved0 >= 3 * len(recs[0].ohlcv)
    assert backend.panel_cache._c_hits["host"].value - host_hits0 >= 3
    assert backend.panel_cache._c_hits["device"].value - dev_hits0 >= 3
    # The spans say so too (obs.timeline's panel_cache_hit pseudo-stage
    # and the h2d-skip report key on these attrs).
    ring = obs.recent_spans()
    assert any(s.get("name") == "worker.decode" and s.get("cache_hit")
               for s in ring), "no cache_hit decode span reached the ring"
    assert any(s.get("name") == "worker.d2h" and s.get("cache_hit")
               for s in ring), "no device-cache-hit d2h span in the ring"

    # Digest-only dispatch must not change a single metric bit vs the
    # directly-computed sweep.
    panel = type(one)(*(jnp.asarray(f)[None, :] for f in one))
    want = sweep.jit_sweep(
        panel, base.get_strategy("sma_crossover"),
        sweep.product_grid(**dict(sorted(GRID.items()))), cost=1e-3)
    for rec in recs:
        got = wire.metrics_from_bytes(
            (tmp_path / "results" / f"{rec.id}.dbxm").read_bytes())
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0], rtol=2e-4, atol=2e-5,
                err_msg=name)


def test_digest_only_miss_recovers_via_fetch_payload(tmp_path, monkeypatch):
    """Third leg of graceful degradation: a worker whose cache cannot
    retain anything (DBX_PANEL_CACHE_MB=0) receives digest-only jobs,
    misses, and recovers the bytes by content address over FetchPayload —
    every job still completes; none fail, none wedge."""
    monkeypatch.setenv("DBX_PANEL_CACHE_MB", "0")
    _, recs = _shared_panel_jobs(4, seed=12)
    queue = JobQueue()
    for rec in recs:
        queue.enqueue(rec)
    disp, srv = _server(queue, results_dir=str(tmp_path / "results"))
    backend = compute.JaxSweepBackend(use_fused=True)
    assert backend.panel_cache.max_bytes == 0
    fetch_hits0 = disp._c_fetches["hit"].value
    try:
        w, t = _run_worker(f"localhost:{srv.port}", backend,
                           jobs_per_chip=1)
        _wait(lambda: queue.drained, msg="queue drained")
        t.join(timeout=10)
    finally:
        srv.stop()
    assert w.jobs_completed == 4
    assert queue.stats()["jobs_failed"] == 0
    # Deliveries 2..4 were digest-only; each recovered via FetchPayload.
    assert disp._c_fetches["hit"].value - fetch_hits0 >= 3
    assert len(list((tmp_path / "results").glob("*.dbxm"))) == 4


def test_digest_only_requires_worker_capability_flag(tmp_path):
    """Rolling-upgrade safety: a client that does NOT set
    JobsRequest.accepts_digest_only (an older worker binary, proto3
    default false) always receives full payload bytes — even for a panel
    the dispatcher already delivered to it — because it has no
    FetchPayload to recover an empty ohlcv with."""
    import grpc

    from distributed_backtesting_exploration_tpu.rpc import service

    _, recs = _shared_panel_jobs(3, seed=13)
    queue = JobQueue()
    for rec in recs:
        queue.enqueue(rec)
    disp, srv = _server(queue, results_dir=str(tmp_path / "results"))
    channel = grpc.insecure_channel(f"localhost:{srv.port}",
                                    options=service.default_channel_options())
    stub = service.DispatcherStub(channel)
    try:
        got = []
        for _ in range(3):
            reply = stub.RequestJobs(pb.JobsRequest(
                worker_id="legacy", chips=1, jobs_per_chip=1))
            got.extend(reply.jobs)
        assert len(got) == 3
        # Every delivery ships the full panel; digests still ride along
        # (harmless to a reader that ignores unknown fields).
        assert all(j.ohlcv == recs[0].ohlcv for j in got)
        assert all(j.panel_digest == recs[0].panel_digest for j in got)
        for j in got:
            disp.CompleteJob(pb.CompleteRequest(
                id=j.id, worker_id="legacy"), None)
        # The capable path on the SAME dispatcher still dedupes.
        _, recs2 = _shared_panel_jobs(2, seed=14)
        for rec in recs2:
            queue.enqueue(rec)
        full = []
        for _ in range(2):
            reply = stub.RequestJobs(pb.JobsRequest(
                worker_id="capable", chips=1, jobs_per_chip=1,
                accepts_digest_only=True))
            full.extend(reply.jobs)
        assert len(full) == 2
        assert full[0].ohlcv == recs2[0].ohlcv
        assert full[1].ohlcv == b"" and full[1].panel_digest
    finally:
        channel.close()
        srv.stop()


# ---------------------------------------------------------------------------
# Streaming appends (AppendBars): O(ΔT) live-bar serving
# ---------------------------------------------------------------------------

def _stream_setup(n_bars=160, base_bars=128, seed=42):
    """One full synthetic history + its base/delta DBX1 slices."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobRecord)
    from distributed_backtesting_exploration_tpu.utils import data

    full = data.synthetic_ohlcv(1, n_bars, seed=seed)
    def cut(lo, hi):
        return data.to_wire_bytes(
            type(full)(*(np.asarray(f[0, lo:hi]) for f in full)))
    rec = JobRecord(id=f"stream-base-{seed}", strategy="sma_crossover",
                    grid=GRID, ohlcv=cut(0, base_bars))
    return full, rec, cut


def _cold_stream_metrics(full, n_bars):
    """The cold streaming sweep over the first n_bars — the parity
    target every append result must match."""
    from distributed_backtesting_exploration_tpu.parallel import sweep
    from distributed_backtesting_exploration_tpu.streaming import (
        recurrent as rc)

    grid = {k: np.asarray(v) for k, v in sweep.product_grid(
        **dict(sorted(GRID.items()))).items()}
    return rc.finalize(rc.build_carry(
        "sma_crossover",
        {"close": np.asarray(full.close)[:, :n_bars]}, grid))


def _append(stub, digest, base_len, delta):
    tmpl = pb.JobSpec(strategy="sma_crossover",
                      grid=wire.grid_to_proto(GRID), cost=0.0,
                      periods_per_year=252)
    return stub.AppendBars(pb.AppendRequest(
        worker_id="feed", panel_digest=digest, base_len=base_len,
        delta=delta, job=tmpl))


def test_append_bars_stream_serves_carry_hits_and_matches_cold(tmp_path):
    """The streaming tentpole end to end: a cold sweep leaves no
    checkpoint, so append #1 full-reprices (graceful, not failed) AND
    stores the carry; append #2 advances it in O(ΔT) — asserted via the
    carry-cache counters, the worker append outcomes, the delta-only
    dispatch counter, and the carry_hit span — and both append results
    match the cold streaming sweep at their lengths."""
    import grpc

    from distributed_backtesting_exploration_tpu import obs
    from distributed_backtesting_exploration_tpu.rpc import service

    full, rec, cut = _stream_setup()
    queue = JobQueue()
    queue.enqueue(rec)
    disp, srv = _server(queue, results_dir=str(tmp_path / "results"))
    backend = compute.JaxSweepBackend(use_fused=True)
    hit0 = backend._c_append["carry_hit"].value
    miss0 = backend._c_append["full_reprice"].value
    delta_mode0 = disp._c_payloads["delta"].value
    # Registry counters are global: earlier tests (the pipelined
    # bit-identity append chains) may already have appended.
    ext0 = disp._c_appends["extended"].value
    channel = grpc.insecure_channel(f"localhost:{srv.port}",
                                    options=service.default_channel_options())
    stub = service.DispatcherStub(channel)
    try:
        w, t = _run_worker(f"localhost:{srv.port}", backend,
                           max_idle_polls=None)
        _wait(lambda: queue.drained, msg="base job drained")
        r1 = _append(stub, rec.panel_digest, 128, cut(128, 144))
        assert r1.ok and r1.new_len == 144
        _wait(lambda: queue.drained, msg="append 1 drained")
        r2 = _append(stub, r1.panel_digest, 144, cut(144, 160))
        assert r2.ok and r2.new_len == 160
        _wait(lambda: queue.drained, msg="append 2 drained")
        w.stop()
        t.join(timeout=10)
    finally:
        channel.close()
        srv.stop()
    assert queue.stats()["jobs_failed"] == 0
    assert disp._c_appends["extended"].value - ext0 == 2
    # Append 1: no checkpoint anywhere -> full reprice; append 2: the
    # stored carry advances.
    assert backend._c_append["full_reprice"].value - miss0 == 1
    assert backend._c_append["carry_hit"].value - hit0 == 1
    # The worker held the base panel, so at least one append shipped
    # delta-only (empty ohlcv + append_delta).
    assert disp._c_payloads["delta"].value - delta_mode0 >= 1
    ring = obs.recent_spans()
    assert any(s.get("name") == "worker.append" and s.get("carry_hit")
               for s in ring), "no carry_hit append span in the ring"

    for reply, n_bars in ((r1, 144), (r2, 160)):
        got = wire.metrics_from_bytes(
            (tmp_path / "results" / f"{reply.job_id}.dbxm").read_bytes())
        want = _cold_stream_metrics(full, n_bars)
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0], rtol=2e-5, atol=2e-6,
                err_msg=f"{n_bars}:{name}")


def test_append_bars_restart_replays_delta_chain(tmp_path):
    """Dispatcher restart mid-stream: the journal's `delta` events rebuild
    the append chain, the NEXT append extends the chain's tip (the store
    re-splices lazily), and a fresh worker — no checkpoint — degrades to
    a full reprice, never a failed job."""
    import grpc

    from distributed_backtesting_exploration_tpu.rpc import service
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal
    from distributed_backtesting_exploration_tpu.utils import data

    jpath = str(tmp_path / "stream.jsonl")
    full, rec, cut = _stream_setup(seed=43)
    queue = JobQueue(Journal(jpath))
    queue.enqueue(rec)
    disp, srv = _server(queue, results_dir=str(tmp_path / "res1"))
    channel = grpc.insecure_channel(f"localhost:{srv.port}",
                                    options=service.default_channel_options())
    stub = service.DispatcherStub(channel)
    try:
        backend = compute.JaxSweepBackend(use_fused=True)
        w, t = _run_worker(f"localhost:{srv.port}", backend,
                           max_idle_polls=None)
        _wait(lambda: queue.drained, msg="base drained")
        r1 = _append(stub, rec.panel_digest, 128, cut(128, 144))
        assert r1.ok
        _wait(lambda: queue.drained, msg="append 1 drained")
        w.stop()
        t.join(timeout=10)
    finally:
        channel.close()
        srv.stop()

    # Restart: fresh queue replays the journal (empty panel store, but
    # the delta chain knows how to rebuild the extended panel).
    queue2 = JobQueue(Journal(jpath))
    queue2.restore(jpath)
    blob = queue2.payload_for_digest(r1.panel_digest)
    assert blob is not None
    assert data.from_wire_bytes(blob).n_bars == 144

    disp2, srv2 = _server(queue2, results_dir=str(tmp_path / "res2"))
    channel2 = grpc.insecure_channel(
        f"localhost:{srv2.port}", options=service.default_channel_options())
    stub2 = service.DispatcherStub(channel2)
    try:
        backend2 = compute.JaxSweepBackend(use_fused=True)
        miss0 = backend2._c_append["full_reprice"].value
        w2, t2 = _run_worker(f"localhost:{srv2.port}", backend2,
                             max_idle_polls=None)
        r2 = _append(stub2, r1.panel_digest, 144, cut(144, 160))
        assert r2.ok and r2.new_len == 160
        _wait(lambda: queue2.drained, msg="post-restart append drained")
        w2.stop()
        t2.join(timeout=10)
        # Fresh worker, no checkpoint: degraded full reprice, zero fails.
        assert backend2._c_append["full_reprice"].value - miss0 == 1
        assert queue2.stats()["jobs_failed"] == 0
        got = wire.metrics_from_bytes(
            (tmp_path / "res2" / f"{r2.job_id}.dbxm").read_bytes())
        want = _cold_stream_metrics(full, 160)
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name))[0], rtol=2e-5, atol=2e-6,
                err_msg=name)
    finally:
        channel2.close()
        srv2.stop()


def test_append_bars_reject_outcomes():
    """Stale or malformed appends are explicit ok=false replies with the
    reason — nothing enqueued, nothing failed."""
    _, rec, cut = _stream_setup(seed=44)
    queue = JobQueue()
    queue.enqueue(rec)
    disp = Dispatcher(queue, PeerRegistry())
    try:
        tmpl = pb.JobSpec(strategy="sma_crossover",
                          grid=wire.grid_to_proto(GRID))
        r = disp.AppendBars(pb.AppendRequest(
            worker_id="feed", panel_digest="ffff" * 8, base_len=128,
            delta=cut(128, 144), job=tmpl), None)
        assert not r.ok and r.detail == "base_missing"
        r = disp.AppendBars(pb.AppendRequest(
            worker_id="feed", panel_digest=rec.panel_digest, base_len=99,
            delta=cut(128, 144), job=tmpl), None)
        assert not r.ok and r.detail == "base_len_mismatch"
        assert r.new_len == 128   # the real base length, for re-sync
        r = disp.AppendBars(pb.AppendRequest(
            worker_id="feed", panel_digest=rec.panel_digest, base_len=128,
            delta=b"garbage", job=tmpl), None)
        assert not r.ok and r.detail == "bad_delta"
        # Non-streamable strategies reject synchronously too (pairs
        # cannot ride a one-panel wire) — no job burns a dispatch round
        # trip only to complete empty.
        r = disp.AppendBars(pb.AppendRequest(
            worker_id="feed", panel_digest=rec.panel_digest, base_len=128,
            delta=cut(128, 144),
            job=pb.JobSpec(strategy="pairs",
                           grid=wire.grid_to_proto(GRID))), None)
        assert not r.ok and r.detail == "unsupported_strategy"
        assert queue.stats()["jobs_pending"] == 1   # only the base job
        assert disp._c_appends["base_missing"].value == 1
        assert disp._c_appends["base_len_mismatch"].value == 1
        assert disp._c_appends["bad_delta"].value == 1
        assert disp._c_appends["unsupported_strategy"].value == 1
    finally:
        disp.close()


def test_append_affinity_routes_to_base_holder(tmp_path):
    """RequestJobs placement (round 20, generalizing the round-6
    append-affinity hook): an append job is deferred from a worker that
    does NOT hold the base while the score table ranks the base holder
    better; the holder then receives it delta-only (empty ohlcv +
    append_delta). The deferral is bounded — with the holder gone
    silent the non-holder is served the job in full once the
    DBX_PLACEMENT_DEFER_CAP budget is spent."""
    import grpc

    from distributed_backtesting_exploration_tpu.rpc import service
    from distributed_backtesting_exploration_tpu.sched import (
        placement as sched_placement)

    _, rec, cut = _stream_setup(seed=45)
    queue = JobQueue()
    queue.enqueue(rec)
    disp, srv = _server(queue, prune_window_s=60.0,
                        results_dir=str(tmp_path / "results"))
    channel = grpc.insecure_channel(f"localhost:{srv.port}",
                                    options=service.default_channel_options())
    stub = service.DispatcherStub(channel)
    try:
        def poll(worker):
            # The live table normally refreshes on the decision plane's
            # 50ms daemon tick; rebuild it synchronously here so the
            # test never races the daemon.
            disp.decisions.refresh_placement_table()
            return list(stub.RequestJobs(pb.JobsRequest(
                worker_id=worker, chips=1, jobs_per_chip=4,
                accepts_digest_only=True)).jobs)

        # holder takes (and completes) the base job: its delivered set
        # now contains the base digest — the table's ground truth.
        base_jobs = poll("holder")
        assert len(base_jobs) == 1 and base_jobs[0].ohlcv
        disp.CompleteJobs(pb.CompleteBatch(
            worker_id="holder",
            items=[pb.CompleteItem(id=base_jobs[0].id)]), None)

        r = _append(stub, rec.panel_digest, 128, cut(128, 144))
        assert r.ok
        # The non-holder polls first: the append job is deferred to give
        # the base holder (carry-store hit, no h2d) first claim.
        assert poll("other") == []
        got = poll("holder")
        assert len(got) == 1
        job = got[0]
        assert job.append_parent_digest == rec.panel_digest
        assert job.append_base_len == 128
        assert job.ohlcv == b"" and job.append_delta   # delta-only
        disp.CompleteJobs(pb.CompleteBatch(
            worker_id="holder",
            items=[pb.CompleteItem(id=job.id)]), None)

        # Bounded deferral: with the holder gone silent, a SECOND append
        # reaches the non-holder in full bytes after exactly
        # defer_cap() deferred polls — work-conserving by construction.
        r2 = _append(stub, r.panel_digest, 144, cut(144, 160))
        assert r2.ok
        for _ in range(sched_placement.defer_cap()):
            assert poll("other") == []        # budget burning down
        job2 = poll("other")
        assert len(job2) == 1 and job2[0].ohlcv   # then served, in full
    finally:
        channel.close()
        srv.stop()
