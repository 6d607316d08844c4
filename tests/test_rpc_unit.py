"""Unit tests for the control plane's pure pieces.

SURVEY.md §4 calls out exactly these as the spots the reference left untested
and buggy: batch-split math (its split_off was inverted), liveness windowing,
and job materialization including unreadable files.
"""

import os

import numpy as np
import pytest

from distributed_backtesting_exploration_tpu.ops.metrics import Metrics
from distributed_backtesting_exploration_tpu.rpc import wire
from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
    JobQueue, JobRecord, PeerRegistry, parse_grid, synthetic_jobs)
from distributed_backtesting_exploration_tpu.rpc.journal import Journal


def _mk_jobs(n, **kw):
    return [JobRecord(id=f"j{i}", strategy="sma_crossover",
                      grid={"fast": np.asarray([5.0, 10.0], np.float32)},
                      ohlcv=b"payload", **kw) for i in range(n)]


@pytest.fixture(params=["native", "python"])
def qfactory(request):
    """JobQueue factory parameterized over the state-machine substrate.

    The native C++ DbxJobQueue and the pure-Python fallback must be
    behaviorally identical — every queue lifecycle test below runs against
    BOTH (the contract in cpp/dbx_core.h is "mirrors the Python fallback
    byte for byte")."""
    use_native = request.param == "native"
    if use_native:
        from distributed_backtesting_exploration_tpu.runtime import _core
        if not _core.available():
            pytest.skip("native core not available")

    def make(*args, **kw):
        kw.setdefault("use_native", use_native)
        q = JobQueue(*args, **kw)
        assert q.substrate == request.param
        return q

    return make


def test_take_n_semantics(qfactory):
    """Ask for n, get exactly min(n, len) — the reference handed out len-n."""
    q = qfactory()
    for r in _mk_jobs(5):
        q.enqueue(r)
    got = q.take(3, "w1")
    assert [r.id for r, _ in got] == ["j0", "j1", "j2"]
    got = q.take(10, "w1")
    assert [r.id for r, _ in got] == ["j3", "j4"]
    assert q.take(1, "w1") == []          # empty -> empty, not an error


def test_lease_expiry_requeues_front(qfactory):
    q = qfactory(lease_s=0.0)             # leases expire immediately
    for r in _mk_jobs(2):
        q.enqueue(r)
    q.take(1, "w1")
    assert q.requeue_expired() == ["j0"]
    got = q.take(2, "w2")
    assert [r.id for r, _ in got] == ["j0", "j1"]   # requeued at the front


def test_requeue_worker_on_prune(qfactory):
    q = qfactory(lease_s=60.0)
    for r in _mk_jobs(3):
        q.enqueue(r)
    q.take(2, "w1")
    q.take(1, "w2")
    assert sorted(q.requeue_worker("w1")) == ["j0", "j1"]
    s = q.stats()
    assert s["jobs_pending"] == 2 and s["jobs_leased"] == 1
    assert s["jobs_requeued"] == 2


def test_complete_idempotent_and_unknown(qfactory):
    q = qfactory()
    for r in _mk_jobs(1):
        q.enqueue(r)
    q.take(1, "w1")
    assert q.complete("j0", "w1") == "new"
    assert q.complete("j0", "w1") == "dup"   # duplicate is fine, and visible
    assert q.complete("nope", "w1") == "unknown"
    assert q.stats()["jobs_completed"] == 1
    assert q.drained


def test_take_pushes_batch_back_on_unexpected_error(tmp_path, qfactory,
                                                    monkeypatch):
    # An exception in the pop->lease window that is NOT the triaged
    # unreadable-payload class (OSError/ValueError) must not strand the
    # popped batch: the ids go back to pending and the error propagates.
    # Regression for the batched-take refactor (a stranded batch was
    # invisible to lease expiry and let drained() flip True early).
    import distributed_backtesting_exploration_tpu.rpc.dispatcher as dmod

    q = qfactory()
    q.enqueue(JobRecord(id="pathy", strategy="s", grid={},
                        path=str(tmp_path / "whatever.csv")))

    def boom(path):
        raise RuntimeError("infra hiccup, not an unreadable payload")

    monkeypatch.setattr(dmod, "_read_payload", boom)
    with pytest.raises(RuntimeError, match="infra hiccup"):
        q.take(4, "w1")
    assert not q.drained                   # still pending, not stranded
    assert q.stats()["jobs_pending"] == 1
    monkeypatch.undo()
    got = q.take(4, "w1")                  # unreadable now (missing file)
    assert got == []
    assert q.stats()["jobs_failed"] == 1


def test_unreadable_file_marked_failed(tmp_path, qfactory):
    jpath = str(tmp_path / "journal.jsonl")
    q = qfactory(Journal(jpath))
    q.enqueue(JobRecord(id="bad", strategy="s", grid={},
                        path=str(tmp_path / "missing.csv")))
    q.enqueue(_mk_jobs(1)[0])
    got = q.take(2, "w1")
    assert [r.id for r, _ in got] == ["j0"]   # bad one skipped, not dispatched
    assert q.stats()["jobs_failed"] == 1
    state = Journal.replay(jpath)
    assert state.failed == {"bad"}


def test_journal_replay_roundtrip(tmp_path, qfactory):
    from distributed_backtesting_exploration_tpu.utils import data
    csv_path = tmp_path / "t.csv"
    series = data.synthetic_ohlcv(1, 16, seed=0)
    csv_path.write_bytes(
        data.to_csv_bytes(type(series)(*(f[0] for f in series))))
    jpath = str(tmp_path / "journal.jsonl")
    q = qfactory(Journal(jpath))
    for r in _mk_jobs(3, path=None):
        r.ohlcv = None
        r.path = str(csv_path)
        q.enqueue(r)
    q.take(3, "w1")
    q.complete("j1", "w1")

    q2 = qfactory()
    restored = q2.restore(jpath)
    assert restored == 2                      # j0, j2 pending again
    ids = {r.id for r, _ in q2.take(5, "w2")}
    assert ids == {"j0", "j2"}


def test_journal_tolerates_torn_tail(tmp_path):
    jpath = tmp_path / "journal.jsonl"
    jpath.write_text(
        '{"ev":"enqueue","id":"a","strategy":"s","grid":{}}\n'
        '{"ev":"enqueue","id":"b","strategy":"s","grid":{}}\n'
        '{"ev":"comp')                        # crash mid-append
    state = Journal.replay(str(jpath))
    assert set(state.jobs) == {"a", "b"} and state.pending == ["a", "b"]


def test_peer_registry_prune(monkeypatch):
    # Pure-Python backend: the C++ registry keeps its own steady clock and
    # cannot see the monkeypatched time.
    reg = PeerRegistry(prune_window_s=10.0, use_native=False)
    t = [100.0]
    monkeypatch.setattr("time.monotonic", lambda: t[0])
    assert reg.touch("w1", chips=4) is True
    assert reg.touch("w1") is False
    t[0] = 105.0
    reg.touch("w2", chips=8)
    t[0] = 111.0                              # w1 silent 11s, w2 6s
    assert reg.prune() == ["w1"]
    assert reg.alive() == 1


def test_peer_registry_prune_native():
    import time as time_mod

    from distributed_backtesting_exploration_tpu.runtime import _core
    if not _core.available():
        pytest.skip("native core not available")
    reg = PeerRegistry(prune_window_s=0.15, use_native=True)
    assert reg.substrate == "native"
    assert reg.touch("w1", chips=4) is True
    assert reg.touch("w1") is False
    time_mod.sleep(0.08)
    reg.touch("w2", chips=8)
    time_mod.sleep(0.1)                       # w1 silent 0.18s, w2 0.1s
    assert reg.prune() == ["w1"]
    assert reg.alive() == 1


def test_metrics_wire_roundtrip():
    m = Metrics(*(np.arange(4, dtype=np.float32) + i
                  for i in range(len(Metrics._fields))))
    back = wire.metrics_from_bytes(wire.metrics_to_bytes(m))
    for a, b in zip(m, back):
        np.testing.assert_array_equal(np.asarray(a), b)
    with pytest.raises(ValueError):
        wire.metrics_from_bytes(b"XXXX" + b"\0" * 16)


def test_parse_grid():
    g = parse_grid("fast=5:8,slow=30:50:10,k=1.5;2.0")
    np.testing.assert_array_equal(g["fast"], [5, 6, 7])
    np.testing.assert_array_equal(g["slow"], [30, 40])
    np.testing.assert_array_equal(g["k"], [1.5, 2.0])
    assert parse_grid("") == {}


def test_synthetic_jobs_decode():
    from distributed_backtesting_exploration_tpu.utils import data
    jobs = synthetic_jobs(2, 64, "sma_crossover",
                          parse_grid("fast=3:5,slow=10:12"))
    assert len(jobs) == 2 and jobs[0].combos == 4
    series = data.from_wire_bytes(jobs[0].ohlcv)
    assert series.n_bars == 64


def test_late_completion_of_pending_job_removes_it(qfactory):
    """A completion racing a requeue (dispatcher restart / expired lease)
    must remove the job from pending and clear any fresh lease."""
    q = qfactory(lease_s=60.0)
    for r in _mk_jobs(2):
        q.enqueue(r)
    # j0 completed while still pending (late RPC after a restart replay):
    assert q.complete("j0", "w1") == "new"
    got = q.take(5, "w2")
    assert [r.id for r, _ in got] == ["j1"], "completed job must not dispatch"
    # duplicate completion of a re-leased job clears the lease:
    q.complete("j1", "w2")
    q.complete("j1", "w3")
    assert q.drained


def test_complete_batch_outcomes(tmp_path, qfactory):
    """complete_batch: one state-machine crossing per batch, per-id
    outcomes identical to complete(), 'new' completions journaled."""
    jpath = str(tmp_path / "journal.jsonl")
    q = qfactory(Journal(jpath))
    for r in _mk_jobs(3):
        q.enqueue(r)
    q.take(3, "w1")
    assert q.complete_batch(["j0", "j1", "nope"], "w1") == \
        ["new", "new", "unknown"]
    assert q.complete_batch(["j0", "j2"], "w1") == ["dup", "new"]
    assert q.complete_batch([], "w1") == []
    assert q.stats()["jobs_completed"] == 3
    assert q.drained
    assert Journal.replay(jpath).completed == {"j0", "j1", "j2"}


def test_batch_commit_drops_mid_take_completion(qfactory):
    """The take race model, batch-wide: an id completed between
    take_begin_n and take_commit_n is dropped (tombstone cleared), the
    rest of the batch leases normally."""
    q = qfactory()
    st = q._state
    # Drive the state machine directly (register + FIFO push): JobQueue
    # itself now parks pending ids in per-tenant WFQ lanes and keeps
    # this FIFO empty between calls — the take-window race contract
    # under test belongs to the substrate, not the lane index.
    for i in range(3):
        st.register(f"j{i}", 2.0)
        st.push_pending(f"j{i}")
    jids = st.take_begin_n(3)
    assert jids == ["j0", "j1", "j2"]
    assert st.take_begin_n(1) == []          # FIFO drained by the batch
    assert st.complete("j1") == "new"        # lands in the take window
    assert st.take_commit_n(jids, "w1", 60.0) == [True, False, True]
    s = st.stats()
    assert s["leased"] == 2 and s["completed"] == 1
    # the dropped id's orphan tombstone is cleared: draining the leases
    # drains the queue.
    assert st.complete("j0") == "new" and st.complete("j2") == "new"
    assert st.drained()


def test_inline_job_survives_journal_restart(tmp_path, qfactory):
    """Synthetic (inline-payload) jobs must be dispatchable after replay."""
    jpath = str(tmp_path / "journal.jsonl")
    q = qfactory(Journal(jpath))
    rec = synthetic_jobs(1, 32, "sma_crossover", parse_grid("fast=3:5"))[0]
    q.enqueue(rec)
    q2 = qfactory()
    assert q2.restore(jpath) == 1
    got = q2.take(1, "w")
    assert len(got) == 1 and got[0][1] == rec.ohlcv


def test_job_with_no_source_fails_cleanly(qfactory):
    q = qfactory()
    q.enqueue(JobRecord(id="x", strategy="s", grid={}))
    assert q.take(1, "w") == []
    assert q.stats()["jobs_failed"] == 1


def test_grid_from_proto_canonical_order():
    """Proto3 map iteration order is unspecified; the wire contract pins
    sorted-by-name axis order so DBXM param ordering is deterministic."""
    import numpy as np
    from distributed_backtesting_exploration_tpu.rpc import backtesting_pb2 as pb
    from distributed_backtesting_exploration_tpu.rpc import wire

    spec = pb.JobSpec(id="g")
    # Insert in reverse-sorted order; decode must come back sorted.
    spec.grid["slow"].values.extend([50.0, 100.0])
    spec.grid["fast"].values.extend([5.0, 10.0])
    spec.grid["alpha"].values.extend([0.1])
    out = wire.grid_from_proto(spec.grid)
    assert list(out) == ["alpha", "fast", "slow"]
    np.testing.assert_array_equal(out["fast"], np.float32([5.0, 10.0]))


def test_backend_fused_bollinger_matches_generic():
    """A bollinger job routed through the fused kernel (interpret mode on
    CPU) must produce the same DBXM payload as the generic sweep path."""
    import numpy as np
    from distributed_backtesting_exploration_tpu.rpc import compute, wire
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        synthetic_jobs)
    from distributed_backtesting_exploration_tpu.rpc import backtesting_pb2 as pb

    grid = {"window": np.float32([10, 20]), "k": np.float32([1.0, 2.0])}
    recs = synthetic_jobs(2, 160, "bollinger", grid, cost=1e-3, seed=11)
    specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                        grid=wire.grid_to_proto(r.grid), cost=r.cost)
             for r in recs]

    fused_backend = compute.JaxSweepBackend(use_fused=True)
    generic_backend = compute.JaxSweepBackend(use_fused=False)
    got_f = {c.job_id: c.metrics for c in fused_backend.process(specs)}
    got_g = {c.job_id: c.metrics for c in generic_backend.process(specs)}
    assert set(got_f) == {r.id for r in recs}
    for jid in got_f:
        mf = wire.metrics_from_bytes(got_f[jid])
        mg = wire.metrics_from_bytes(got_g[jid])
        for name in mf._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(mf, name)), np.asarray(getattr(mg, name)),
                rtol=2e-4, atol=2e-5, err_msg=name)


def _write_csv(path, n_bars=16, seed=0):
    from distributed_backtesting_exploration_tpu.utils import data
    s = data.synthetic_ohlcv(1, n_bars, seed=seed)
    path.write_bytes(data.to_csv_bytes(type(s)(*(f[0] for f in s))))


def test_complete_during_take_window_no_tombstone_leak(tmp_path, monkeypatch,
                                                       qfactory):
    """ADVICE r2 (medium): a completion landing between take()'s FIFO pop
    and lease creation installed a permanent tombstone, after which
    jobs_pending under-counted and drained never flipped True."""
    from distributed_backtesting_exploration_tpu.rpc import (
        dispatcher as disp)

    csv_path = tmp_path / "t.csv"
    _write_csv(csv_path)
    q = qfactory()
    q.enqueue(disp.JobRecord(id="j0", strategy="s", grid={},
                             path=str(csv_path)))
    orig = disp._read_payload

    def complete_mid_take(path):
        # take() reads the payload outside its lock — exactly the window
        # the race needs.
        q.complete("j0", "late-worker")
        return orig(path)

    monkeypatch.setattr(disp, "_read_payload", complete_mid_take)
    assert q.take(1, "w1") == []          # completed job must not dispatch
    s = q.stats()
    assert s["jobs_pending"] == 0 and s["jobs_leased"] == 0
    assert s["jobs_completed"] == 1
    assert q.drained                      # used to hang at live_pending == -1


def test_complete_during_failed_read_not_marked_failed(tmp_path, monkeypatch,
                                                       qfactory):
    """Same window, but the payload read fails: a job completed mid-take
    must count as completed, not failed."""
    from distributed_backtesting_exploration_tpu.rpc import (
        dispatcher as disp)

    q = qfactory()
    q.enqueue(disp.JobRecord(id="j0", strategy="s", grid={},
                             path=str(tmp_path / "gone.csv")))

    def complete_then_fail(path):
        q.complete("j0", "late-worker")
        raise OSError("disk gone")

    monkeypatch.setattr(disp, "_read_payload", complete_then_fail)
    assert q.take(1, "w1") == []
    s = q.stats()
    assert s["jobs_failed"] == 0 and s["jobs_completed"] == 1
    assert q.drained


def test_journal_corrupt_interior_is_loud(tmp_path):
    """ADVICE r1: replay used to skip EVERY undecodable line; an interior
    corrupt enqueue silently dropped a job from recovery."""
    from distributed_backtesting_exploration_tpu.rpc.journal import (
        JournalCorruptError)

    jpath = tmp_path / "j.jsonl"
    jpath.write_text(
        '{"ev":"enqueue","id":"a","strategy":"s","grid":{}}\n'
        'GARBAGE-NOT-JSON\n'
        '{"ev":"enqueue","id":"b","strategy":"s","grid":{}}\n')
    with pytest.raises(JournalCorruptError):
        Journal.replay(str(jpath))
    state = Journal.replay(str(jpath), strict=False)
    assert state.corrupt_lines == 1
    assert set(state.jobs) == {"a", "b"}
    # The benign torn-tail case stays tolerated in strict mode:
    jpath.write_text(
        '{"ev":"enqueue","id":"a","strategy":"s","grid":{}}\n'
        '{"ev":"comp')
    assert Journal.replay(str(jpath)).pending == ["a"]


def test_restart_does_not_duplicate_file_jobs(tmp_path):
    """ADVICE r1 (medium): rerunning the documented command line after a
    crash re-enqueued every --data path under fresh UUIDs."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    for name in ("a", "b"):
        _write_csv(tmp_path / f"{name}.csv")
    argv = ["--data", str(tmp_path / "*.csv"),
            "--journal", str(tmp_path / "j.jsonl"),
            "--grid", "fast=3:5,slow=8:10"]
    d1 = build_dispatcher(make_parser().parse_args(argv))
    got = d1.queue.take(10, "w")
    assert len(got) == 2
    done_id, survivor_id = got[0][0].id, got[1][0].id
    d1.queue.complete(done_id, "w")

    # Crash (d1 dropped) + restart with the SAME argv:
    d2 = build_dispatcher(make_parser().parse_args(argv))
    assert d2.queue.stats()["jobs_pending"] == 1
    ids = [r.id for r, _ in d2.queue.take(10, "w2")]
    assert ids == [survivor_id], "only the unfinished job may re-dispatch"


def test_restart_does_not_reseed_synthetic(tmp_path):
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    argv = ["--synthetic", "3", "--bars", "32",
            "--journal", str(tmp_path / "j.jsonl"),
            "--grid", "fast=3:5,slow=8:10"]
    d1 = build_dispatcher(make_parser().parse_args(argv))
    assert d1.queue.stats()["jobs_pending"] == 3
    got = d1.queue.take(1, "w")
    d1.queue.complete(got[0][0].id, "w")

    d2 = build_dispatcher(make_parser().parse_args(argv))
    assert d2.queue.stats()["jobs_pending"] == 2   # restored, not 3 + 2


def test_completion_retry_never_blocks_control_thread():
    """ADVICE r1: completion retry used to sleep 0.2+1+5s inline on the
    control thread, starving heartbeats past the prune window."""
    import time
    from types import SimpleNamespace

    import grpc

    from distributed_backtesting_exploration_tpu.rpc import compute
    from distributed_backtesting_exploration_tpu.rpc.worker import Worker

    w = Worker("localhost:1", compute.InstantBackend())
    w._next_status = time.monotonic() + 60.0      # heartbeat not due
    calls = []

    class FlakyStub:
        fail = 2

        def CompleteJobs(self, req, timeout=None):
            calls.extend(i.id for i in req.items)
            if self.fail:
                self.fail -= 1
                raise grpc.RpcError()
            return SimpleNamespace(accepted=len(req.items), unknown_ids=[])

    stub = FlakyStub()
    w._out.put(compute.Completion("j1", b"", 0.0))
    t0 = time.monotonic()
    w._drain_completions(stub)                    # attempt 1 fails -> parks
    assert time.monotonic() - t0 < 0.2, "drain must not sleep"
    assert len(w._deferred) == 1 and w.jobs_completed == 0
    w._drain_completions(stub)                    # not due yet: no attempt
    assert len(calls) == 1

    def force_due():
        w._deferred = [(time.monotonic() - 1, a, c)
                       for _, a, c in w._deferred]

    force_due()
    w._drain_completions(stub)                    # attempt 2 fails -> parks
    force_due()
    w._drain_completions(stub)                    # attempt 3 succeeds
    assert w.jobs_completed == 1 and not w._deferred
    assert w.completions_dropped == 0


def test_completion_drain_yields_to_overdue_heartbeat():
    import time

    from distributed_backtesting_exploration_tpu.rpc import compute
    from distributed_backtesting_exploration_tpu.rpc.worker import Worker

    w = Worker("localhost:1", compute.InstantBackend())
    w._next_status = time.monotonic() - 1.0       # heartbeat overdue

    class NeverCalled:
        def CompleteJobs(self, req, timeout=None):
            raise AssertionError("drain must yield to the heartbeat first")

    w._out.put(compute.Completion("j1", b"", 0.0))
    w._drain_completions(NeverCalled())           # returns without attempting
    assert w.jobs_completed == 0


def test_completion_dropped_after_attempts_exhausted():
    import time

    import grpc

    from distributed_backtesting_exploration_tpu.rpc import compute
    from distributed_backtesting_exploration_tpu.rpc.worker import Worker

    w = Worker("localhost:1", compute.InstantBackend())
    w._next_status = time.monotonic() + 60.0

    class DeadStub:
        def CompleteJobs(self, req, timeout=None):
            raise grpc.RpcError()

    stub = DeadStub()
    w._out.put(compute.Completion("j1", b"", 0.0))
    for _ in range(1 + len(Worker._COMPLETION_BACKOFF_S)):
        w._drain_completions(stub)
        w._deferred = [(time.monotonic() - 1, a, c)
                       for _, a, c in w._deferred]
    assert w.completions_dropped == 1 and not w._deferred


def test_worker_channel_asks_for_no_compression(monkeypatch):
    """Requests go out uncompressed (rpc/service.py: gzip of result blocks
    costs more CPU than the bytes it saves): neither the shared channel
    options nor the channel ``Worker.run`` opens may ask for compression."""
    import grpc

    from distributed_backtesting_exploration_tpu.rpc import compute, service
    from distributed_backtesting_exploration_tpu.rpc.worker import Worker

    assert not any("compression" in key
                   for key, _ in service.default_channel_options())
    w = Worker("localhost:1", compute.InstantBackend())
    opened = []

    class StoppingChannel:
        """Every RPC stops the worker and fails: run() ends after one loop."""

        def unary_unary(self, *args, **kwargs):
            return self._call

        unary_stream = unary_unary

        def _call(self, *args, **kwargs):
            w.stop()
            raise grpc.RpcError()

        def close(self):
            pass

    def insecure_channel(target, *args, **kwargs):
        opened.append((target, args, kwargs))
        return StoppingChannel()

    monkeypatch.setattr(grpc, "insecure_channel", insecure_channel)
    w.run()
    [(target, args, kwargs)] = opened
    assert target == "localhost:1"
    options = kwargs.get("options", args[0] if args else ())
    assert not any("compression" in key for key, _ in options)
    assert kwargs.get("compression") in (
        None, grpc.Compression.NoCompression)


def test_native_substrate_defaults(monkeypatch):
    """The C++ core backs the live paths where it measures fastest: the
    registry and worker channels default native; the job-queue state
    machine defaults PYTHON by measurement (CPython's dict/deque beat the
    ctypes-driven core at Python-call grain even after the batch/
    int-handle redesign — DESIGN.md "queue state machine alone"), with
    ``DBX_NATIVE_QUEUE=1`` / ``use_native=True`` opting in. The native
    machine remains the only substrate at the C ABI (cpp/dbx_core_bench:
    ~1.1M jobs/s there)."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobQueue, PeerRegistry)
    from distributed_backtesting_exploration_tpu.rpc.worker import Worker
    from distributed_backtesting_exploration_tpu.rpc import compute
    from distributed_backtesting_exploration_tpu.runtime import _core

    if not _core.available():
        pytest.skip("native core not available")
    monkeypatch.delenv("DBX_NATIVE_QUEUE", raising=False)
    assert JobQueue().substrate == "python"
    monkeypatch.setenv("DBX_NATIVE_QUEUE", "1")
    assert JobQueue().substrate == "native"
    assert JobQueue(use_native=True).substrate == "native"
    assert PeerRegistry().substrate == "native"
    w = Worker("localhost:1", compute.InstantBackend())
    assert w._in.backend == "native" and w._out.backend == "native"


def test_oversized_job_id_rejected_at_intake(qfactory):
    """Ids beyond the native substrate's 511-byte cap are rejected at
    enqueue on BOTH substrates — behavior must not diverge at the edge
    (and a half-registered record must not strand in _records)."""
    q = qfactory()
    big = JobRecord(id="x" * 600, strategy="s", grid={}, ohlcv=b"p")
    with pytest.raises(ValueError, match="511 bytes"):
        q.enqueue(big)
    assert q.stats()["jobs_pending"] == 0
    assert q.complete(big.id, "w") == "unknown"   # nothing half-registered


def test_journal_compaction_preserves_live_state(tmp_path):
    """Compaction drops terminal jobs' payload blobs but preserves exactly
    what recovery and tooling need: pending payloads, completed/failed ids
    (idempotency + tombstones), paths (restart dedupe), and grids
    (aggregation joins)."""
    import json
    import os

    import numpy as np

    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        JobQueue, synthetic_jobs)
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal

    jp = str(tmp_path / "j.jsonl")
    queue = JobQueue(Journal(jp))
    grid = {"fast": np.asarray([3.0], np.float32),
            "slow": np.asarray([8.0], np.float32)}
    recs = synthetic_jobs(4, 64, "sma_crossover", grid, seed=2)
    for rec in recs:
        queue.enqueue(rec)
    queue.take(2, "w1")
    assert queue.complete(recs[0].id, "w1") == "new"
    assert queue.complete(recs[1].id, "w1") == "new"

    size_before = os.path.getsize(jp)
    before, after = Journal.compact(jp)
    assert before >= after       # line count can only shrink...
    assert os.path.getsize(jp) < size_before   # ...and payload bytes MUST

    state = Journal.replay(jp)
    assert set(state.pending) == {recs[2].id, recs[3].id}
    assert state.completed == {recs[0].id, recs[1].id}
    # Completed jobs keep grid (aggregation) but lose the payload.
    done_rec = state.jobs[recs[0].id]
    assert "ohlcv_b64" not in done_rec and "grid" in done_rec
    # Pending jobs keep their full inline payload.
    assert "ohlcv_b64" in state.jobs[recs[2].id]

    # A restored queue behaves identically: pending re-dispatches with
    # payload intact, duplicate completion stays idempotent.
    q2 = JobQueue()
    assert q2.restore(jp) == 2
    taken = q2.take(2, "w2")
    assert {r.id for r, _ in taken} == {recs[2].id, recs[3].id}
    assert all(payload for _, payload in taken)
    assert q2.complete(recs[0].id, "w2") == "dup"
    # Compacted output is well-formed JSONL throughout.
    with open(jp) as fh:
        for line in fh:
            json.loads(line)


def test_journal_compaction_idempotent_and_empty(tmp_path):
    from distributed_backtesting_exploration_tpu.rpc.journal import Journal

    assert Journal.compact(str(tmp_path / "missing.jsonl")) == (0, 0)
    jp = str(tmp_path / "j.jsonl")
    j = Journal(jp)
    j.append("enqueue", id="a", strategy="sma_crossover", grid={})
    j.close()
    b1, a1 = Journal.compact(jp)
    b2, a2 = Journal.compact(jp)
    assert (b2, a2) == (a1, a1)   # second pass is a no-op rewrite


def test_backend_fused_multifield_strategies_match_generic():
    """donchian_hl and vwap_reversion jobs route to fused kernels that
    consume non-close columns (high/low, volume); the backend must ship
    those columns and produce the generic path's DBXM payload."""
    import numpy as np
    from distributed_backtesting_exploration_tpu.rpc import compute, wire
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        synthetic_jobs)
    from distributed_backtesting_exploration_tpu.rpc import backtesting_pb2 as pb

    cases = [
        ("donchian_hl", {"window": np.float32([10, 20])}),
        ("vwap_reversion", {"window": np.float32([8, 16]),
                            "k": np.float32([1.0, 2.0])}),
    ]
    for strategy, grid in cases:
        recs = synthetic_jobs(2, 160, strategy, grid, cost=1e-3, seed=21)
        specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                            grid=wire.grid_to_proto(r.grid), cost=r.cost)
                 for r in recs]
        fused_backend = compute.JaxSweepBackend(use_fused=True)
        assert fused_backend._fused_eligible(
            specs[0], wire.grid_from_proto(specs[0].grid), [160]), strategy
        got_f = {c.job_id: c.metrics
                 for c in fused_backend.process(specs)}
        got_g = {c.job_id: c.metrics
                 for c in compute.JaxSweepBackend(use_fused=False
                                                  ).process(specs)}
        assert set(got_f) == {r.id for r in recs}
        for jid in got_f:
            mf = wire.metrics_from_bytes(got_f[jid])
            mg = wire.metrics_from_bytes(got_g[jid])
            for name in mf._fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(mf, name)),
                    np.asarray(getattr(mg, name)),
                    rtol=2e-4, atol=2e-5, err_msg=f"{strategy}/{name}")


def test_backend_fused_donchian_hl_big_window_stays_generic():
    """Windows beyond models.donchian.MAX_WINDOW poison the generic
    (semantics-defining) path to NaN; the hl router must not let the fused
    kernel silently diverge there."""
    import numpy as np
    from distributed_backtesting_exploration_tpu.models import donchian
    from distributed_backtesting_exploration_tpu.rpc import compute

    class _Job:
        strategy = "donchian_hl"

    grid = {"window": np.float32([10, donchian.MAX_WINDOW + 1])}
    assert not compute.JaxSweepBackend._fused_eligible(_Job(), grid, [160])


def test_fused_demotion_to_generic_path_is_loud(caplog):
    """A job a VMEM/table cap silently routes off the fused kernel is a
    throughput bug nobody can see: submit() must log one warning per job
    group naming the cap that demoted it (round-3 verdict: the >128-window
    and >8192-bar demotions were silent)."""
    import logging
    import numpy as np
    from distributed_backtesting_exploration_tpu.rpc import compute, wire
    from distributed_backtesting_exploration_tpu.rpc import backtesting_pb2 as pb
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        synthetic_jobs)

    backend = compute.JaxSweepBackend(use_fused=True)

    def run(strategy, grid, caplog):
        recs = synthetic_jobs(1, 96, strategy, grid, seed=3)
        specs = [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                            ohlcv2=r.ohlcv2 or b"",
                            grid=wire.grid_to_proto(r.grid))
                 for r in recs]
        with caplog.at_level(logging.WARNING, logger="dbx.compute"):
            backend.process(specs)
        return [r.message for r in caplog.records if "demoted" in r.message]

    # 130 distinct windows exceed the 128-entry selection-table cap.
    wide = {"lookback": np.arange(1, 131, dtype=np.float32)}
    msgs = run("momentum", wide, caplog)
    assert msgs and "130 distinct table windows" in msgs[0]
    assert str(compute.JaxSweepBackend._FUSED_MAX_WINDOWS) in msgs[0]

    # The two-legged path has its own router; it must be loud too.
    caplog.clear()
    pair_grid = {"lookback": np.float32([10.5]),
                 "z_entry": np.float32([1.0])}
    msgs = run("pairs", pair_grid, caplog)
    assert msgs and "non-integral lookback" in msgs[0]

    # An eligible job logs nothing (demotion warnings must not cry wolf).
    caplog.clear()
    ok = {"lookback": np.float32([5, 10])}
    assert run("momentum", ok, caplog) == []


def test_wf_test_without_train_not_stamped(tmp_path):
    """--wf-test without --wf-train must not stamp inert wf fields on
    records (they would split worker co-batching across a restart)."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    args = make_parser().parse_args(
        ["--synthetic", "2", "--bars", "64", "--grid", "fast=3,slow=8",
         "--wf-test", "30", "--results-dir", str(tmp_path)])
    disp = build_dispatcher(args)
    taken = disp.queue.take(2, "w")
    assert len(taken) == 2
    for rec, _ in taken:
        assert (rec.wf_train, rec.wf_test, rec.wf_metric) == (0, 0, "")


def test_make_backend_forwards_fused_and_mesh_flags():
    from distributed_backtesting_exploration_tpu.rpc.worker import (
        make_backend)

    b = make_backend("jax", use_fused=False, use_mesh=True, param_chunk=4)
    assert b.use_fused is False and b.param_chunk == 4
    assert b._mesh is not None          # 8 virtual devices in tests
    b2 = make_backend("jax", use_fused=None, use_mesh=False)
    assert b2._mesh is None


def test_topk_wire_roundtrip():
    """DBXS block: indices + k metric rows + the rank metric's name."""
    idx = np.asarray([5, 2, 9], np.int32)
    m = Metrics(*(np.arange(3, dtype=np.float32) + i for i in range(9)))
    blob = wire.topk_to_bytes(idx, m, "sortino")
    gi, gm, metric = wire.topk_from_bytes(blob)
    assert metric == "sortino"
    np.testing.assert_array_equal(gi, idx)
    for a, b in zip(gm, m):
        np.testing.assert_array_equal(a, b)
    # Kind classification covers all three payload shapes.
    assert wire.result_kind(blob) == "topk"
    assert wire.result_kind(wire.metrics_to_bytes(m)) == "metrics"
    assert wire.result_kind(b"") == "empty"
    with pytest.raises(ValueError, match="magic"):
        wire.result_kind(b"????rest")
    with pytest.raises(ValueError, match="truncated"):
        wire.topk_from_bytes(blob[:-4])
    with pytest.raises(ValueError, match="magic"):
        wire.topk_from_bytes(wire.metrics_to_bytes(m))


def test_topk_fields_travel_journal_and_cli(tmp_path):
    """JobRecord.top_k/rank_metric survive the journal round trip and the
    CLI stamps them only in sweep mode (walk-forward + --top-k is an
    error; unknown --rank-metric is an error)."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    rec = JobRecord(id="t", strategy="sma_crossover",
                    grid={"fast": np.float32([3.0])}, ohlcv=b"x",
                    top_k=8, rank_metric="cagr")
    back = JobRecord.from_journal(rec.journal_form())
    assert (back.top_k, back.rank_metric) == (8, "cagr")
    # Default stays zero-valued (no "topk" journal key).
    assert "topk" not in JobRecord(
        id="u", strategy="s", grid={}, ohlcv=b"x").journal_form()

    args = make_parser().parse_args(
        ["--synthetic", "2", "--bars", "64", "--grid", "fast=3,slow=8",
         "--top-k", "4", "--rank-metric", "sortino",
         "--results-dir", str(tmp_path)])
    disp = build_dispatcher(args)
    for rec, _ in disp.queue.take(2, "w"):
        assert (rec.top_k, rec.rank_metric) == (4, "sortino")

    with pytest.raises(SystemExit, match="rank-metric"):
        build_dispatcher(make_parser().parse_args(
            ["--synthetic", "1", "--top-k", "4", "--rank-metric", "nope",
             "--results-dir", str(tmp_path)]))
    with pytest.raises(SystemExit, match="walk-forward"):
        build_dispatcher(make_parser().parse_args(
            ["--synthetic", "1", "--top-k", "4", "--wf-train", "50",
             "--wf-test", "20", "--results-dir", str(tmp_path)]))


def test_topk_reduce_ranks_nan_last_and_respects_direction():
    """_topk_reduce: NaN metric cells rank behind every finite one (a
    zero-variance backtest has NaN sharpe — it must not win top-k by NaN
    comparison accident), and lower-is-better metrics rank ascending."""
    import numpy as np

    from distributed_backtesting_exploration_tpu.rpc.compute import (
        _topk_reduce)

    P = 6
    fields = {name: np.arange(P, dtype=np.float32)[None, :] + i
              for i, name in enumerate(Metrics._fields)}
    sharpe = np.float32([[0.5, np.nan, 2.0, np.nan, 1.0, -3.0]])
    fields["sharpe"] = sharpe
    m = Metrics(**fields)

    idx, sel = _topk_reduce(m, "sharpe", 4)
    np.testing.assert_array_equal(np.asarray(idx)[0], [2, 4, 0, 5])
    np.testing.assert_array_equal(np.asarray(sel.sharpe)[0],
                                  sharpe[0][[2, 4, 0, 5]])
    # Non-ranking fields travel with their row.
    np.testing.assert_array_equal(
        np.asarray(sel.turnover)[0],
        np.asarray(m.turnover)[0][[2, 4, 0, 5]])

    # Lower-is-better direction: max_drawdown picks the smallest values.
    mdd = np.float32([[0.5, 0.1, np.nan, 0.3, 0.2, 0.9]])
    fields["max_drawdown"] = mdd
    m2 = Metrics(**fields)
    idx2, sel2 = _topk_reduce(m2, "max_drawdown", 3)
    np.testing.assert_array_equal(np.asarray(idx2)[0], [1, 4, 3])


def _write_leg_csvs(tmp_path, n, t=64, prefix=""):
    from distributed_backtesting_exploration_tpu.utils import data as dmod

    batch = dmod.synthetic_ohlcv(n, t, seed=11)
    paths = []
    for i in range(n):
        one = dmod.OHLCV(*(f[i] for f in batch))
        p = tmp_path / f"{prefix}{i}.csv"
        p.write_bytes(dmod.to_csv_bytes(one))
        paths.append(str(p))
    return paths


def test_file_backed_pairs_jobs(tmp_path):
    """--data/--data2: pairs jobs take leg y and leg x from matched files,
    materialized at dispatch time; an unreadable leg-x file marks the job
    failed (not silently dropped); path2 survives the journal."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)
    from distributed_backtesting_exploration_tpu.utils import data as dmod

    ys = _write_leg_csvs(tmp_path, 2, prefix="y")
    xs = _write_leg_csvs(tmp_path, 2, prefix="x")
    args = make_parser().parse_args(
        ["--strategy", "pairs", "--data", str(tmp_path / "y*.csv"),
         "--data2", str(tmp_path / "x*.csv"),
         "--grid", "lookback=6;10,z_entry=0.8;1.5",
         "--results-dir", str(tmp_path / "res"),
         "--journal", str(tmp_path / "q.jsonl")])
    disp = build_dispatcher(args)
    taken = disp.queue.take(2, "w")
    assert len(taken) == 2
    for (rec, payload), yp, xp in zip(taken, sorted(ys), sorted(xs)):
        assert rec.path == yp and rec.path2 == xp
        y = dmod.from_wire_bytes(payload)
        x = dmod.from_wire_bytes(rec.ohlcv2)
        assert y.n_bars == x.n_bars == 64

    # Journal round trip keeps path2.
    back = JobRecord.from_journal(taken[0][0].journal_form())
    assert back.path2 == taken[0][0].path2

    # Unreadable leg-x -> failed, journaled, leg y was readable.
    import os
    os.unlink(xs[0])
    args2 = make_parser().parse_args(
        ["--strategy", "pairs", "--data", str(tmp_path / "y*.csv"),
         "--data2", str(tmp_path / "x*.csv"),
         "--grid", "lookback=6",
         "--results-dir", str(tmp_path / "res2")])
    import pytest as _pytest
    with _pytest.raises(SystemExit, match="matched"):
        build_dispatcher(args2)   # glob count mismatch is loud

    # Same count, one unreadable: job fails at take time.
    bad = tmp_path / "x0.csv"
    bad.write_bytes(b"not,a,csv\n1,2\n")
    disp3 = build_dispatcher(make_parser().parse_args(
        ["--strategy", "pairs", "--data", str(tmp_path / "y*.csv"),
         "--data2", str(tmp_path / "x*.csv"), "--grid", "lookback=6",
         "--results-dir", str(tmp_path / "res3")]))
    taken3 = disp3.queue.take(2, "w")
    assert len(taken3) == 1            # the good pair
    assert disp3.queue.stats()["jobs_failed"] == 1


def test_data2_flag_validation(tmp_path):
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    with pytest.raises(SystemExit, match="data2"):
        build_dispatcher(make_parser().parse_args(
            ["--strategy", "pairs", "--data", "nope*.csv",
             "--results-dir", str(tmp_path)]))
    with pytest.raises(SystemExit, match="pairs-only"):
        build_dispatcher(make_parser().parse_args(
            ["--strategy", "sma_crossover", "--data", "a*.csv",
             "--data2", "b*.csv", "--results-dir", str(tmp_path)]))
    with pytest.raises(SystemExit, match="leg-y"):
        build_dispatcher(make_parser().parse_args(
            ["--strategy", "pairs", "--data2", "b*.csv",
             "--results-dir", str(tmp_path)]))


def test_inline_leg_y_with_file_leg_x_journal_roundtrip():
    """A record with an inline leg-y payload and a file-backed leg-x must
    journal BOTH (regression: the path2 key once swallowed the inline
    ohlcv_b64 branch, so a restart restored a job with nothing to
    dispatch)."""
    rec = JobRecord(id="m", strategy="pairs",
                    grid={"lookback": np.float32([6.0])},
                    ohlcv=b"leg-y-bytes", path2="/tmp/x.csv")
    form = rec.journal_form()
    assert "ohlcv_b64" in form and form["path2"] == "/tmp/x.csv"
    back = JobRecord.from_journal(form)
    assert back.ohlcv == b"leg-y-bytes" and back.path2 == "/tmp/x.csv"


def test_pairs_restart_with_vanished_leg_file_still_serves(tmp_path):
    """Crash-restart discipline: when every pair is already journaled, a
    since-deleted leg-x file must not SystemExit the dispatcher — the
    restored queue is the workload and nothing new needs the pairing."""
    import os

    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    ys = _write_leg_csvs(tmp_path, 2, prefix="y")
    xs = _write_leg_csvs(tmp_path, 2, prefix="x")
    argv = ["--strategy", "pairs", "--data", str(tmp_path / "y*.csv"),
            "--data2", str(tmp_path / "x*.csv"), "--grid", "lookback=6",
            "--journal", str(tmp_path / "q.jsonl"),
            "--results-dir", str(tmp_path / "res")]
    disp = build_dispatcher(make_parser().parse_args(argv))
    assert disp.queue.stats()["jobs_pending"] == 2

    os.unlink(xs[0])   # leg file vanishes between runs
    disp2 = build_dispatcher(make_parser().parse_args(argv))
    s = disp2.queue.stats()
    assert s["jobs_pending"] == 2          # restored, not re-enqueued


def test_result_block_short_header_raises_valueerror():
    """ADVICE r3: a blob with valid magic but a truncated header must raise
    the contract's ValueError, not leak struct.error into an aggregate run
    (same gap class the differential fuzz closed in data.from_wire_bytes)."""
    for n in range(4, 13):
        with pytest.raises(ValueError, match="truncated"):
            wire.topk_from_bytes(b"DBXS" + b"\x00" * (n - 4))
    for n in range(4, 12):
        with pytest.raises(ValueError, match="truncated"):
            wire.metrics_from_bytes(b"DBXM" + b"\x00" * (n - 4))
    # Header intact but the rank-metric name itself is cut off.
    m = Metrics(*(np.float32([1.0, 2.0]) for _ in range(9)))
    blob = wire.topk_to_bytes(np.int32([0, 1]), m, "sortino")
    with pytest.raises(ValueError, match="truncated"):
        wire.topk_from_bytes(blob[:15])   # 13-byte header + 2 of 7 name bytes


def test_pairs_glob_churn_keeps_journaled_x_legs(tmp_path, caplog):
    """ADVICE r3: y-glob churn between runs with equal counts must not
    silently re-assign an x leg that a journaled pair already claimed —
    the journal's (y, x) pairing is authoritative; ambiguity is loud."""
    import logging
    import os

    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    ys = _write_leg_csvs(tmp_path, 2, prefix="y")
    xs = _write_leg_csvs(tmp_path, 2, prefix="x")
    argv = ["--strategy", "pairs", "--data", str(tmp_path / "y*.csv"),
            "--data2", str(tmp_path / "x*.csv"), "--grid", "lookback=6",
            "--journal", str(tmp_path / "q.jsonl"),
            "--results-dir", str(tmp_path / "res")]
    disp = build_dispatcher(make_parser().parse_args(argv))
    pairing = {r.path: r.path2 for r, _ in disp.queue.take(2, "w")}
    assert pairing == dict(zip(sorted(ys), sorted(xs)))

    # Churn: y0 deleted, y2 added; x set unchanged, counts still equal.
    # Positional pairing would hand y2 the x leg journaled for y1; the
    # fixed intake refuses instead of silently re-assigning.
    os.unlink(ys[0])
    _write_leg_csvs(tmp_path, 3, prefix="y")       # recreates y0,y1 + new y2
    os.unlink(ys[0])                                # keep y0 deleted
    with caplog.at_level(logging.WARNING, logger="dbx.dispatcher"), \
            pytest.raises(SystemExit, match="already paired"):
        build_dispatcher(make_parser().parse_args(argv))
    assert any("churn" in r.message for r in caplog.records)

    # Matching churn on BOTH legs: the new y pairs with the one x no
    # journaled pair has claimed — regardless of sort position.
    _write_leg_csvs(tmp_path, 3, prefix="x")
    os.unlink(xs[0])
    disp3 = build_dispatcher(make_parser().parse_args(argv))
    taken = disp3.queue.take(10, "w2")
    new = [r for r, _ in taken if r.path == str(tmp_path / "y2.csv")]
    assert len(new) == 1
    assert new[0].path2 == str(tmp_path / "x2.csv")
    # The restored y1 job keeps its journaled x1 leg.
    old = [r for r, _ in taken if r.path == str(tmp_path / "y1.csv")]
    assert old and old[0].path2 == str(tmp_path / "x1.csv")


def test_pairs_restart_with_stray_unclaimed_x_still_serves(tmp_path):
    """Code-review r4: a pure crash-restart with a stray unclaimed leg-x
    file (user dropped an extra x into the glob) must serve the restored
    queue, not die on a paths/paths2 length mismatch."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    _write_leg_csvs(tmp_path, 2, prefix="y")
    _write_leg_csvs(tmp_path, 2, prefix="x")
    argv = ["--strategy", "pairs", "--data", str(tmp_path / "y*.csv"),
            "--data2", str(tmp_path / "x*.csv"), "--grid", "lookback=6",
            "--journal", str(tmp_path / "q.jsonl"),
            "--results-dir", str(tmp_path / "res")]
    disp = build_dispatcher(make_parser().parse_args(argv))
    assert disp.queue.stats()["jobs_pending"] == 2

    _write_leg_csvs(tmp_path, 3, prefix="x")   # stray x2.csv appears
    disp2 = build_dispatcher(make_parser().parse_args(argv))
    assert disp2.queue.stats()["jobs_pending"] == 2   # restored + served


def test_best_returns_wire_roundtrip():
    """DBXP block: grid index + one metric row + the net-return series."""
    row = Metrics(*(np.float32(i + 0.5) for i in range(9)))
    ret = np.linspace(-0.01, 0.01, 37).astype(np.float32)
    blob = wire.best_returns_to_bytes(7, row, ret, "sharpe")
    gi, gm, gr, metric = wire.best_returns_from_bytes(blob)
    assert gi == 7 and metric == "sharpe"
    for a, b in zip(gm, row):
        assert float(a) == float(b)
    np.testing.assert_array_equal(gr, ret)
    assert wire.result_kind(blob) == "returns"
    # Truncation at every boundary raises the contract's ValueError, never
    # struct.error (the DBX1/DBXS decoder discipline).
    for cut in (4, 10, 16, 18, 22, len(blob) - 1):
        with pytest.raises(ValueError, match="truncated|magic"):
            wire.best_returns_from_bytes(blob[:cut])
    with pytest.raises(ValueError, match="magic"):
        wire.best_returns_from_bytes(wire.metrics_to_bytes(
            Metrics(*(np.zeros(1, np.float32) for _ in range(9)))))


def test_best_returns_travels_journal_and_cli(tmp_path):
    """JobRecord.best_returns survives the journal round trip; the CLI
    rejects the incompatible mode combinations."""
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        build_dispatcher, make_parser)

    rec = JobRecord(id="p", strategy="sma_crossover",
                    grid={"fast": np.float32([3.0])}, ohlcv=b"x",
                    best_returns=True, rank_metric="cagr")
    back = JobRecord.from_journal(rec.journal_form())
    assert back.best_returns is True and back.rank_metric == "cagr"
    # Plain records stay untouched.
    plain = JobRecord.from_journal(JobRecord(
        id="q", strategy="s", grid={}, ohlcv=b"x").journal_form())
    assert plain.best_returns is False

    parser = make_parser()
    base_args = ["--synthetic", "1", "--grid", "fast=3,slow=8"]
    for bad in (["--best-returns", "--top-k", "4"],
                ["--best-returns", "--wf-train", "50", "--wf-test", "10"],
                ["--best-returns", "--strategy", "pairs"],
                ["--best-returns", "--rank-metric", "nope"]):
        with pytest.raises(SystemExit):
            build_dispatcher(parser.parse_args(base_args + bad))
    args = parser.parse_args(base_args + ["--best-returns",
                                          "--journal",
                                          str(tmp_path / "j.jsonl")])
    disp = build_dispatcher(args)
    taken = disp.queue.take(1, "w")
    assert taken and taken[0][0].best_returns is True


def test_best_returns_rejected_for_pairs_and_walkforward():
    """A hand-built best_returns spec on pairs or walk-forward jobs is
    validated-bad: complete-with-empty, loudly, no requeue loop."""
    from distributed_backtesting_exploration_tpu.rpc import (
        backtesting_pb2 as pb, compute)
    from distributed_backtesting_exploration_tpu.utils import data

    backend = compute.JaxSweepBackend(use_fused=False)
    one = data.synthetic_ohlcv(1, 48, seed=2)
    ohlcv = data.to_wire_bytes(type(one)(*(f[0] for f in one)))
    specs = [
        pb.JobSpec(id="p1", strategy="pairs", ohlcv=ohlcv, ohlcv2=ohlcv,
                   grid=wire.grid_to_proto(
                       {"lookback": np.float32([8.0]),
                        "z_entry": np.float32([1.0])}),
                   best_returns=True),
        pb.JobSpec(id="w1", strategy="sma_crossover", ohlcv=ohlcv,
                   grid=wire.grid_to_proto({"fast": np.float32([3.0]),
                                            "slow": np.float32([8.0])}),
                   wf_train=24, wf_test=8, best_returns=True),
    ]
    comps = backend.process(specs)
    assert sorted(c.job_id for c in comps) == ["p1", "w1"]
    assert all(c.metrics == b"" for c in comps)


def test_best_returns_unknown_metric_completes_empty():
    from distributed_backtesting_exploration_tpu.rpc import (
        backtesting_pb2 as pb, compute)
    from distributed_backtesting_exploration_tpu.utils import data

    one = data.synthetic_ohlcv(1, 48, seed=3)
    ohlcv = data.to_wire_bytes(type(one)(*(f[0] for f in one)))
    spec = pb.JobSpec(id="m1", strategy="sma_crossover", ohlcv=ohlcv,
                      grid=wire.grid_to_proto({"fast": np.float32([3.0]),
                                               "slow": np.float32([8.0])}),
                      best_returns=True, rank_metric="not_a_metric")
    comps = compute.JaxSweepBackend(use_fused=False).process([spec])
    assert len(comps) == 1 and comps[0].metrics == b""


# ---------------------------------------------------------------------------
# Dispatch by digest: content-addressed panel store (dispatcher side)
# ---------------------------------------------------------------------------

def test_file_backed_job_redispatches_after_source_deleted(tmp_path,
                                                           qfactory):
    """Regression for the requeue re-read bug: a file-backed (CSV) job used
    to re-read AND re-transcode its source on every dispatch — with the
    content-addressed blob store, a requeued job dispatches from memory
    even after the source file is deleted post-first-materialization."""
    import os

    from distributed_backtesting_exploration_tpu.utils import data

    csv_path = tmp_path / "t.csv"
    series = data.synthetic_ohlcv(1, 16, seed=5)
    csv_path.write_bytes(
        data.to_csv_bytes(type(series)(*(f[0] for f in series))))
    q = qfactory(lease_s=60.0)
    rec = JobRecord(id="f1", strategy="sma_crossover",
                    grid={"fast": np.asarray([5.0], np.float32)},
                    path=str(csv_path))
    q.enqueue(rec)
    assert rec.panel_digest == ""          # file-backed: stamped at take
    taken = q.take(1, "w1")
    assert len(taken) == 1
    first_payload = taken[0][1]
    digest = rec.panel_digest
    assert digest and first_payload[:4] == b"DBX1"

    # Lease abandoned, source deleted: the redispatch must come from the
    # store, not the (gone) file, under the SAME content address.
    assert q.requeue_worker("w1") == ["f1"]
    os.remove(csv_path)
    taken2 = q.take(1, "w2")
    assert len(taken2) == 1
    assert taken2[0][1] == first_payload
    assert rec.panel_digest == digest
    # And FetchPayload's backing lookup serves it too.
    assert q.payload_for_digest(digest) == first_payload


def test_panel_digest_journaled_and_restored(tmp_path, qfactory):
    """The digest stamped at first materialization survives a restart (a
    "digest" journal event merges into the enqueue record on replay), so
    a restarted dispatcher keeps addressing the panel the first run
    delivered; the empty store repopulates lazily from the source."""
    from distributed_backtesting_exploration_tpu.utils import data

    csv_path = tmp_path / "t.csv"
    series = data.synthetic_ohlcv(1, 16, seed=6)
    csv_path.write_bytes(
        data.to_csv_bytes(type(series)(*(f[0] for f in series))))
    jpath = str(tmp_path / "journal.jsonl")
    q = qfactory(Journal(jpath))
    rec = JobRecord(id="f1", strategy="sma_crossover",
                    grid={"fast": np.asarray([5.0], np.float32)},
                    path=str(csv_path))
    q.enqueue(rec)
    (payload,) = [p for _, p in q.take(1, "w1")]
    assert rec.panel_digest
    q.requeue_worker("w1")

    q2 = qfactory()
    assert q2.restore(jpath) == 1
    (taken,) = q2.take(1, "w2")
    assert taken[0].panel_digest == rec.panel_digest
    assert taken[1] == payload
    # Inline payloads journal their digest with the enqueue record.
    q3 = qfactory(Journal(str(tmp_path / "j2.jsonl")))
    inline = _mk_jobs(1)[0]
    q3.enqueue(inline)
    assert inline.panel_digest
    q4 = qfactory()
    assert q4.restore(str(tmp_path / "j2.jsonl")) == 1
    (taken4,) = q4.take(1, "w1")
    assert taken4[0].panel_digest == inline.panel_digest


def test_panel_store_lru_bound_and_unservable_digest(tmp_path):
    """The store honors its byte bound (LRU eviction), and an evicted
    digest whose source is also gone is reported unservable (None) — the
    FetchPayload leg that makes the dispatcher forget the delivery."""
    from distributed_backtesting_exploration_tpu.rpc.panel_store import (
        PanelStore, panel_digest)

    store = PanelStore(max_bytes=64)
    d1 = store.put(b"a" * 40)
    d2 = store.put(b"b" * 40)           # evicts the first blob
    assert store.get(d2) == b"b" * 40
    assert store.get(d1) is None
    assert store.stats()["evictions"] == 1
    assert store.stats()["bytes"] <= 64
    assert d1 == panel_digest(b"a" * 40)

    # Queue-level: digest known, store evicted, file gone -> unservable.
    from distributed_backtesting_exploration_tpu.utils import data

    csv_path = tmp_path / "t.csv"
    series = data.synthetic_ohlcv(1, 16, seed=7)
    csv_path.write_bytes(
        data.to_csv_bytes(type(series)(*(f[0] for f in series))))
    q = JobQueue()
    rec = JobRecord(id="f1", strategy="sma_crossover",
                    grid={"fast": np.asarray([5.0], np.float32)},
                    path=str(csv_path))
    q.enqueue(rec)
    q.take(1, "w1")
    q.panel_store.max_bytes = 0
    q.panel_store.put(b"x")             # force the eviction sweep
    import os

    os.remove(csv_path)
    assert q.payload_for_digest(rec.panel_digest) is None


# ---------------------------------------------------------------------------
# Streaming appends: queue half (chain, journal, affinity)
# ---------------------------------------------------------------------------

def _stream_base(n_bars=64, seed=21):
    from distributed_backtesting_exploration_tpu.utils import data

    full = data.synthetic_ohlcv(1, n_bars + 16, seed=seed)

    def cut(lo, hi):
        return data.to_wire_bytes(
            type(full)(*(np.asarray(f[0, lo:hi]) for f in full)))

    rec = JobRecord(id="sb", strategy="sma_crossover",
                    grid=parse_grid("fast=3:5,slow=10:14:2"),
                    ohlcv=cut(0, n_bars))
    return rec, cut


def test_append_bars_chain_journal_and_compaction(tmp_path, qfactory):
    """append_bars journals an O(ΔT) `delta` event (never the extended
    panel), the chain survives replay AND compaction, and a restarted
    queue re-materializes the extended panel bit-identically — same
    content digest — even with an empty panel store."""
    from distributed_backtesting_exploration_tpu.rpc import panel_store
    from distributed_backtesting_exploration_tpu.utils import data

    jp = str(tmp_path / "s.jsonl")
    rec, cut = _stream_base()
    q = qfactory(Journal(jp))
    q.enqueue(rec)
    arec, outcome, ndig, new_len = q.append_bars(
        rec.panel_digest, 64, cut(64, 72), strategy="sma_crossover",
        grid=rec.grid)
    assert outcome == "extended" and new_len == 72
    assert arec.append_parent == rec.panel_digest
    assert arec.ohlcv is None and arec.path is None
    # Journal growth is O(ΔT): no line carries the 72-bar extended panel.
    extended = data.splice_wire_bytes(cut(0, 64), cut(64, 72))
    assert panel_store.panel_digest(extended) == ndig
    import base64 as b64
    blob64 = b64.b64encode(extended).decode()
    with open(jp) as fh:
        assert all(blob64 not in line for line in fh)

    # Drain the base job so compaction has something to fold; the append
    # job stays pending.
    got = q.take(1, "w")
    assert [r.id for r, _ in got] == [rec.id]
    q.complete_batch([rec.id], "w")

    Journal.compact(jp)
    q2 = qfactory(None)
    assert q2.restore(jp) == 1            # the pending append job
    # Store empty after restart: payload_for_digest rebuilds via chain.
    blob = q2.payload_for_digest(ndig)
    assert blob == extended
    # take() of the restored append job materializes through the chain.
    taken = q2.take(1, "w2")
    assert len(taken) == 1
    trec, payload = taken[0]
    assert trec.append_parent == rec.panel_digest and payload == extended


def test_append_bars_base_gone_is_explicit_reject(qfactory):
    rec, cut = _stream_base(seed=22)
    q = qfactory(None)
    q.enqueue(rec)
    _, outcome, _, _ = q.append_bars(
        "00" * 16, 64, cut(64, 72), strategy="sma_crossover",
        grid=rec.grid)
    assert outcome == "base_missing"
    assert q.stats()["jobs_pending"] == 1   # nothing new enqueued


def test_take_admit_defers_then_serves(qfactory):
    """The placement hook's contract (round 20, generalizing the old
    append-only affinity hook): EVERY popped record is consulted — the
    placement stage ranks ordinary jobs too — a rejected job is held OUT
    of the batch (and the FIFO) for that call, re-queued front-of-line
    afterwards, and an admit that keeps rejecting cannot lose a job.
    ``drained`` must stay False while anything is held."""
    rec, cut = _stream_base(seed=23)
    q = qfactory(None)
    q.enqueue(rec)
    arec, outcome, ndig, _ = q.append_bars(
        rec.panel_digest, 64, cut(64, 72), strategy="sma_crossover",
        grid=rec.grid)
    assert outcome == "extended"

    consulted = []

    def deny(r):
        consulted.append(r.id)
        r.affinity_skips += 1
        return False

    got = q.take(4, "w", admit=deny)
    # Both jobs consulted, both deferred — nothing served this call.
    assert got == []
    assert sorted(consulted) == sorted([rec.id, arec.id])
    # Held jobs still count as in-take: an observer must not tear the
    # dispatcher down while placement holds the whole queue.
    assert not q.drained
    # Deferred, not lost: a later take (any admit verdict) serves both,
    # the held pair first in line.
    got2 = q.take(4, "w", admit=lambda r: True)
    assert {r.id for r, _ in got2} == {rec.id, arec.id}
    assert ndig in {r.panel_digest for r, _ in got2}
    q.complete_batch([r.id for r, _ in got2], "w")
    assert q.drained


def test_append_chain_long_stream_survives_restart(tmp_path, qfactory):
    """A long live stream (many chained appends) must stay servable after
    a restart: the chain walk is iterative, so payload reconstruction
    works at any chain length and re-stores every level on the way up."""
    from distributed_backtesting_exploration_tpu.utils import data

    jp = str(tmp_path / "long.jsonl")
    n0, dt, links = 48, 4, 12
    full = data.synthetic_ohlcv(1, n0 + dt * links, seed=31)

    def cut(lo, hi):
        return data.to_wire_bytes(
            type(full)(*(np.asarray(f[0, lo:hi]) for f in full)))

    rec = JobRecord(id="long-base", strategy="sma_crossover",
                    grid=parse_grid("fast=3:5,slow=10:14:2"),
                    ohlcv=cut(0, n0))
    q = qfactory(Journal(jp))
    q.enqueue(rec)
    dig, L = rec.panel_digest, n0
    for _ in range(links):
        arec, outcome, dig, L = q.append_bars(
            dig, L, cut(L, L + dt), strategy="sma_crossover",
            grid=rec.grid)
        assert outcome == "extended"

    q2 = qfactory(Journal(jp))
    q2.restore(jp)
    blob = q2.payload_for_digest(dig)
    assert blob is not None
    assert data.from_wire_bytes(blob).n_bars == n0 + dt * links
    # Restored append jobs keep their delta bytes (delta-only dispatch
    # works across restarts, not just in the first process).
    restored = [r for r in q2._records.values() if r.append_parent]
    assert restored and all(r.delta for r in restored)
