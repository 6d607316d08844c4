"""Fleet telemetry plane (obs/fleet.py, round 15): frame-merge
determinism, the staleness flag -> evict lifecycle against a stopped
worker, straggler flags, merged-histogram exactness vs a single-process
registry, hostile worker ids through the bounded bucket map, the
`dbxtop` surfaces (--url CLIs), and the DBX_LOCKDEP zero-violations
gate — all in-process (tier-1 budget discipline)."""

import contextlib
import io
import json
import threading
import time

import numpy as np
import pytest

from distributed_backtesting_exploration_tpu import obs
from distributed_backtesting_exploration_tpu.obs import fleet
from distributed_backtesting_exploration_tpu.obs.registry import (
    Histogram, Registry)
from distributed_backtesting_exploration_tpu.rpc import compute
from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
    Dispatcher, DispatcherServer, JobQueue, PeerRegistry, synthetic_jobs)
from distributed_backtesting_exploration_tpu.rpc.worker import Worker
from distributed_backtesting_exploration_tpu.sched import tenancy

GRID = {"fast": np.arange(5.0, 9.0, dtype=np.float32)}


def _wait(pred, timeout=30.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _frame(gen="g1", pid=1, seq=1, t=1000.0, jobs=10, rate=2.5,
           stages=None, proc=None, caches=None, proc_id=None):
    """A hand-built telemetry frame (the schema is the wire contract).
    ``proc_id`` omitted exercises the pre-token fallback (dedupe keys
    on pid)."""
    doc = {
        "v": 1, "gen": gen, "pid": pid, "seq": seq, "t": t,
        "uptime_s": 5.0, "busy": 1, "inflight": 1,
        "pipeline": {"on": True, "depth": 2},
        "jobs_completed": jobs, "completions_dropped": 0, "polls": seq,
        "jobs_per_s": rate, "caps": {"backend": "test", "chips": 1},
        "caches": caches or {}, "proc": proc or {},
        "stages": stages or {}}
    if proc_id is not None:
        doc["proc_id"] = proc_id
    return json.dumps(doc, sort_keys=True)


def _stage_frame_stats(durs, stage="execute"):
    """Accumulate ``durs`` through a REAL worker-side stage collector
    and return its frame form — the exact accumulation the worker
    ships."""
    st = fleet._StageStats()
    for d in durs:
        st.observe({"name": f"worker.{stage}"
                    if stage != "execute" else "worker.execute",
                    "dur_s": d})
    return st.snapshot()


# ---------------------------------------------------------------------------
# Merge determinism
# ---------------------------------------------------------------------------

def test_frame_merge_is_order_independent():
    """Same frames in ANY arrival order => byte-identical snapshots:
    per generation the highest seq wins, across generations the later
    (t, gen) wins, and the snapshot is a pure function of the retained
    frames + now. This is the contract the placement scorer (ROADMAP
    item 3) and any future shard-to-shard gossip trust."""
    frames = [
        ("w-a", _frame(gen="a1", seq=1, t=1000.0, jobs=5)),
        ("w-a", _frame(gen="a1", seq=3, t=1002.0, jobs=20)),
        ("w-a", _frame(gen="a1", seq=2, t=1001.0, jobs=12)),
        # A RESTARTED w-a: new generation, later wall stamp — must win
        # over every a1 frame regardless of order.
        ("w-a", _frame(gen="a2", seq=1, t=1005.0, jobs=2)),
        ("w-b", _frame(gen="b1", seq=1, t=1000.5, jobs=7)),
        ("w-b", _frame(gen="b1", seq=2, t=1001.5, jobs=9)),
    ]
    import itertools

    snaps = set()
    for perm in itertools.permutations(range(len(frames))):
        fv = fleet.FleetView(registry=Registry(), clock=lambda: 50.0)
        for i in perm:
            fv.update(*frames[i])
        snaps.add(json.dumps(fv.snapshot(now=50.0), sort_keys=True))
    assert len(snaps) == 1
    snap = json.loads(next(iter(snaps)))
    assert snap["workers"]["w-a"]["gen"] == "a2"
    assert snap["workers"]["w-a"]["jobs_completed"] == 2
    assert snap["workers"]["w-b"]["seq"] == 2
    assert snap["fleet"]["jobs_completed"] == 11


def test_malformed_frames_are_counted_never_raised():
    reg = Registry()
    fv = fleet.FleetView(registry=reg, clock=lambda: 0.0)
    assert not fv.update("w", "not json{")
    assert not fv.update("w", json.dumps(["no", "gen"]))
    assert not fv.update("w", "")
    # JSON-valid but ill-typed fields are malformed too: adopting one
    # would poison every later snapshot() (the int()/float() folds),
    # turning /fleet.json and GetStats into permanent 500s.
    assert not fv.update("w", json.dumps({"gen": "g", "busy": "yes"}))
    assert not fv.update("w", json.dumps({"gen": "g", "seq": "x"}))
    assert not fv.update("w", json.dumps(
        {"gen": "g", "stages": {"execute": {"n": "NaN?"}}}))
    assert not fv.update("w", json.dumps({"gen": "g", "caps": "fast"}))
    # Python's json.loads parses bare NaN/Infinity tokens: non-finite
    # numerics are malformed too (a NaN jobs_per_s would make the fleet
    # rollup NaN and re-serialize as invalid JSON for strict parsers;
    # a NaN t defeats _frame_order — every comparison False).
    assert not fv.update("w", '{"gen": "g", "jobs_per_s": NaN}')
    assert not fv.update("w", '{"gen": "g", "t": Infinity}')
    assert not fv.update("w", '{"gen": "g", "busy": Infinity}')
    assert not fv.update("w", json.dumps(
        {"gen": "g", "stages": {"execute": {"ewma_s": float("inf")}}}))
    assert reg.counter("dbx_fleet_frames_total",
                       outcome="malformed").value == 10
    assert fv.snapshot(now=0.0)["fleet"]["workers"] == 0
    # A corrected follow-up frame heals the worker (nothing poisoned).
    assert fv.update("w", _frame(gen="g2"))
    assert fv.snapshot(now=0.0)["fleet"]["workers"] == 1


def test_unknown_frame_fields_skip_and_count():
    """Forward compatibility (round 17): a frame from a NEWER worker
    carrying fields this dispatcher doesn't know is adopted — the known
    fields merge, the unknown ones are skipped and counted
    (dbx_fleet_frame_unknown_fields_total + a per-worker flag in the
    snapshot/dbxtop), never treated as malformed. The alternative —
    rejecting the frame — would black out telemetry for every worker
    one release ahead of its dispatcher."""
    reg = Registry()
    fv = fleet.FleetView(registry=reg, clock=lambda: 0.0)
    doc = json.loads(_frame())
    doc["shiny_new_field"] = {"whatever": 1}
    doc["another_future_key"] = 2
    assert fv.update("w-f", json.dumps(doc, sort_keys=True))
    snap = fv.snapshot(now=0.0)
    assert snap["workers"]["w-f"]["unknown_fields"] == 2
    assert snap["workers"]["w-f"]["jobs_completed"] == 10
    assert reg.peek("dbx_fleet_frame_unknown_fields_total") == 2
    assert "+2fields" in fleet.render_text(snap)
    # A fully-known frame carries no flag at all.
    assert fv.update("w-g", _frame(gen="g2"))
    assert "unknown_fields" not in fv.snapshot(now=0.0)["workers"]["w-g"]


def test_restart_with_backstepped_clock_supersedes_once_stale():
    """A live restarted worker whose wall clock stepped BACKWARD across
    the restart must not be wedged behind its dead generation: while the
    retained entry is fresh the (t, gen) order holds (the lower-t frame
    is superseded), but once the entry passes the staleness bound a
    differing-generation frame is adopted regardless of wall stamps."""
    clock = [100.0]
    fv = fleet.FleetView(registry=Registry(), clock=lambda: clock[0],
                         stale_s_override=1.0)
    assert fv.update("w", _frame(gen="old", seq=9, t=5000.0, jobs=50))
    # Fresh entry: normal precedence — the backstepped frame loses.
    assert not fv.update("w", _frame(gen="new", seq=1, t=4000.0, jobs=1))
    assert fv.snapshot(now=clock[0])["workers"]["w"]["gen"] == "old"
    # Past the staleness bound the old gen has stopped talking — the
    # new generation wins even with the lower wall stamp.
    clock[0] += 2.0
    assert fv.update("w", _frame(gen="new", seq=2, t=4000.1, jobs=2))
    snap = fv.snapshot(now=clock[0])
    assert snap["workers"]["w"]["gen"] == "new"
    assert not snap["workers"]["w"]["stale"]


# ---------------------------------------------------------------------------
# Staleness: flag -> evict against a stopped worker (real gRPC fixture)
# ---------------------------------------------------------------------------

def test_stopped_worker_goes_stale_then_evicted(tmp_path, monkeypatch):
    """Two live workers gossip frames; one stops. Its entry must decay
    visibly — flagged ``stale`` past DBX_FLEET_STALE_S (rollups exclude
    it) — and then be EVICTED by the maintenance loop's prune path past
    3x the bound, while the surviving worker stays live the whole
    time."""
    monkeypatch.setenv("DBX_FLEET_STALE_S", "0.6")
    monkeypatch.setenv("DBX_FLEET_FRAME_MIN_S", "0.05")
    monkeypatch.setenv("DBX_FLEET_HEARTBEAT_S", "0.1")
    queue = JobQueue()
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=60.0),
                      results_dir=str(tmp_path / "results"))
    srv = DispatcherServer(disp, bind="localhost:0",
                           prune_interval_s=0.1).start()
    workers = [Worker(f"localhost:{srv.port}", compute.InstantBackend(),
                      worker_id=f"st-{i}", poll_interval_s=0.05,
                      status_interval_s=0.5, jobs_per_chip=8)
               for i in range(2)]
    threads = [threading.Thread(target=w.run, daemon=True)
               for w in workers]
    try:
        for t in threads:
            t.start()
        for rec in synthetic_jobs(16, 32, "sma_crossover", GRID, seed=3):
            queue.enqueue(rec)
        _wait(lambda: queue.drained, msg="drain")
        _wait(lambda: set(disp.fleet.snapshot()["workers"])
              == {"st-0", "st-1"}, msg="both workers in the fleet view")
        workers[1].stop()
        threads[1].join(timeout=20)
        # Phase 1: flagged stale (still present — visible decay).
        _wait(lambda: disp.fleet.snapshot()["workers"]
              .get("st-1", {}).get("stale") is True,
              msg="stopped worker flagged stale")
        snap = disp.fleet.snapshot()
        assert snap["workers"]["st-0"]["stale"] is False
        assert snap["fleet"]["live"] == 1
        assert snap["fleet"]["stale"] == 1
        # Phase 2: evicted by the maintenance loop past 3x the bound.
        _wait(lambda: "st-1" not in disp.fleet.snapshot()["workers"],
              msg="stale entry evicted by the prune path")
        assert "st-0" in disp.fleet.snapshot()["workers"]
        assert disp.obs.counter(
            "dbx_fleet_workers_evicted_total").value >= 1
    finally:
        for w in workers:
            w.stop()
        for t in threads:
            t.join(timeout=20)
        srv.stop()


def test_peer_prune_forgets_fleet_entry(tmp_path):
    """A peer pruned for silence drops out of the fleet view
    immediately (forget_worker) — no 3x-staleness wait for a worker the
    registry already declared dead."""
    queue = JobQueue()
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=60.0),
                      results_dir=str(tmp_path / "results"))
    disp.fleet.update("gone", _frame())
    assert "gone" in disp.fleet.snapshot()["workers"]
    disp.forget_worker("gone")
    assert "gone" not in disp.fleet.snapshot()["workers"]
    disp.close()


# ---------------------------------------------------------------------------
# Stragglers
# ---------------------------------------------------------------------------

def test_straggler_flagged_on_slowed_worker():
    """The PR-4 timeline rule applied live: a worker whose per-stage
    EWMA exceeds the fleet p95 (from the MERGED histograms, with the
    bucket-noise margin) is flagged in that stage — and only that
    worker, only that stage. The population shape matters: a straggler
    is slow, so it contributes FEW observations while the healthy bulk
    defines the p95 — exactly the regime the live rule serves."""
    fast = _stage_frame_stats([0.001] * 100)
    slow = _stage_frame_stats([0.8] * 4)
    fv = fleet.FleetView(registry=Registry(), clock=lambda: 0.0)
    fv.update("w-fast", _frame(gen="f", pid=1, stages=fast))
    fv.update("w-slow", _frame(gen="s", pid=2, stages=slow))
    snap = fv.snapshot(now=0.0)
    assert snap["workers"]["w-slow"]["stragglers"] == ["execute"]
    assert snap["workers"]["w-fast"]["stragglers"] == []
    # Transition counter ticks once per episode, not per scrape.
    reg = Registry()
    fv2 = fleet.FleetView(registry=reg, clock=lambda: 0.0)
    fv2.update("w-fast", _frame(gen="f", pid=1, stages=fast))
    fv2.update("w-slow", _frame(gen="s", pid=2, stages=slow))
    fv2.collect(reg)
    fv2.collect(reg)
    assert reg.counter("dbx_fleet_straggler_flags_total",
                       stage="execute").value == 1


def test_no_straggler_below_population_floor():
    """p95 of a tiny sample is noise: below MIN_STRAGGLER_OBS merged
    observations (or with a single live worker) nothing is flagged."""
    fv = fleet.FleetView(registry=Registry(), clock=lambda: 0.0)
    fv.update("w-slow", _frame(
        gen="s", pid=2, stages=_stage_frame_stats([0.8] * 3)))
    snap = fv.snapshot(now=0.0)
    assert snap["workers"]["w-slow"]["stragglers"] == []


# ---------------------------------------------------------------------------
# Histogram merge exactness
# ---------------------------------------------------------------------------

def test_merged_stage_histogram_is_exact_vs_single_registry():
    """The fleet fold and a single-process registry histogram see the
    SAME durations => identical count, sum and quantile estimates (the
    bucket bounds are shared and summing per-bucket counts commutes —
    exactness, not approximation)."""
    durs_a = [0.0001, 0.004, 0.004, 0.02, 0.3]
    durs_b = [0.0002, 0.008, 0.05, 1.2, 0.0007, 0.09]
    fv = fleet.FleetView(registry=Registry(), clock=lambda: 0.0)
    fv.update("w-a", _frame(gen="a", pid=1,
                            stages=_stage_frame_stats(durs_a)))
    fv.update("w-b", _frame(gen="b", pid=2,
                            stages=_stage_frame_stats(durs_b)))
    merged = fv.snapshot(now=0.0)["fleet"]["stages"]["execute"]

    # Reference 1: ONE worker-side collector fed every duration.
    ref = fleet._StageStats()
    for d in durs_a + durs_b:
        ref.observe({"name": "worker.execute", "dur_s": d})
    one = ref.snapshot()["execute"]
    assert merged["n"] == one["n"] == len(durs_a) + len(durs_b)
    assert merged["sum_s"] == pytest.approx(one["sum_s"])
    assert merged["p50_s"] == pytest.approx(
        fleet._hist_quantile(one["buckets"], 0.5))
    assert merged["p95_s"] == pytest.approx(
        fleet._hist_quantile(one["buckets"], 0.95))

    # Reference 2: the registry Histogram with the same (shared) bucket
    # bounds holds identical per-bucket counts.
    h = Histogram(fleet.STAGE_BUCKETS_S)
    for d in durs_a + durs_b:
        h.observe(d)
    reg_counts = []
    prev = 0
    for _, acc in h.cumulative():
        reg_counts.append(acc - prev)
        prev = acc
    assert reg_counts == one["buckets"]
    assert h.count == merged["n"]
    assert h.sum == pytest.approx(merged["sum_s"])


def test_collect_lane_spans_fold_onto_execute_and_d2h():
    """The collect lane's split: the device finishing a group costs out
    as execute, the copy back and the packing as d2h; the compute
    thread's waits and the envelopes cost out as no stage."""
    st = fleet._StageStats()
    for name, dur in (("worker.device_wait", 0.25), ("worker.d2h", 0.125),
                      ("worker.pack", 0.5), ("worker.wait_batch", 2.0),
                      ("worker.wait_slot", 1.0), ("worker.collect", 1.0)):
        st.observe({"name": name, "dur_s": dur})
    snap = st.snapshot()
    assert snap["execute"]["n"] == 1
    assert snap["execute"]["sum_s"] == pytest.approx(0.25)
    assert snap["d2h"]["n"] == 2
    assert snap["d2h"]["sum_s"] == pytest.approx(0.625)
    assert snap["decode"]["n"] == snap["compile"]["n"] == 0


def test_cohosted_workers_fold_once_per_pid():
    """Co-hosted workers share one process-scope span stream; the fold
    dedupes per process so a 2-workers-1-process bench cannot
    double-count stage observations (same proc_id token; the bare-pid
    fallback for pre-token frames behaves the same)."""
    shared = _stage_frame_stats([0.01] * 10)
    fv = fleet.FleetView(registry=Registry(), clock=lambda: 0.0)
    fv.update("w-a", _frame(gen="a", pid=7, proc_id="proc-x",
                            stages=shared))
    fv.update("w-b", _frame(gen="b", pid=7, proc_id="proc-x",
                            stages=shared))
    merged = fv.snapshot(now=0.0)["fleet"]["stages"]["execute"]
    assert merged["n"] == 10   # not 20
    # Pre-token frames (no proc_id) fall back to pid-keyed dedupe.
    fv2 = fleet.FleetView(registry=Registry(), clock=lambda: 0.0)
    fv2.update("w-a", _frame(gen="a", pid=7, stages=shared))
    fv2.update("w-b", _frame(gen="b", pid=7, stages=shared))
    assert fv2.snapshot(now=0.0)["fleet"]["stages"]["execute"]["n"] == 10


def test_multihost_pid_collision_does_not_collapse_stats():
    """Bare OS pids collide across hosts (containers all run pid 1):
    frames from DIFFERENT processes that happen to share a pid must
    both count in the fleet fold — the dedupe keys on the host-unique
    proc_id token, not the pid."""
    s1 = _stage_frame_stats([0.01] * 10)
    s2 = _stage_frame_stats([0.02] * 6)
    fv = fleet.FleetView(registry=Registry(), clock=lambda: 0.0)
    fv.update("host-a/w", _frame(gen="a", pid=1, proc_id="proc-a",
                                 stages=s1,
                                 proc={"panel_host": [8, 2]}))
    fv.update("host-b/w", _frame(gen="b", pid=1, proc_id="proc-b",
                                 stages=s2,
                                 proc={"panel_host": [0, 10]}))
    snap = fv.snapshot(now=0.0)
    assert snap["fleet"]["stages"]["execute"]["n"] == 16   # 10 + 6
    # Cache hit counters aggregate across both hosts too: 8/(8+2+0+10).
    assert snap["fleet"]["cache_hit_ratio"]["panel_host"] == 0.4


# ---------------------------------------------------------------------------
# Worker-id cardinality + hostile strings
# ---------------------------------------------------------------------------

def test_hostile_worker_ids_through_bucket_map(monkeypatch):
    """Worker ids are wire-controlled strings: newlines, quotes,
    unicode and kilobyte names must neither break the Prometheus render
    nor mint unbounded label sets — past DBX_WORKER_LABEL_MAX everything
    shares the `other` bucket, and the sticky map stores nothing for
    overflow keys."""
    monkeypatch.setenv("DBX_WORKER_LABEL_MAX", "2")
    tenancy.reset_tenant_buckets()
    reg = Registry()
    fv = fleet.FleetView(registry=reg, clock=lambda: 0.0)
    hostile = ['evil"worker\n# HELP boom', "wörk☃er", "x" * 1024,
               "a\\b", "w-plain"]
    for i, wid in enumerate(hostile):
        fv.update(wid, _frame(gen=f"g{i}", pid=i))
    fv.collect(reg)
    text = reg.render_prometheus()
    # Escaped label values: the embedded newline must never start a
    # line of its own (a raw one would feed the scraper a fake HELP).
    assert not any(line.startswith("# HELP boom")
                   for line in text.splitlines())
    assert r"\n# HELP boom" in text    # escaped form survives in-label
    buckets = {tenancy.worker_bucket(w) for w in hostile}
    assert tenancy.OVERFLOW_BUCKET in buckets
    assert len(buckets) == 3     # 2 sticky names + "other"
    # The JSON surface keeps full ids (per-document, not per-series).
    snap = fv.snapshot(now=0.0)
    assert set(snap["workers"]) == set(hostile)
    json.dumps(snap)             # serializable as served
    tenancy.reset_tenant_buckets()


def test_per_worker_gauges_removed_with_their_workers(monkeypatch):
    """Evicting/forgetting a worker must also retire its per-worker
    gauge series: a dead worker's last jobs/s (or a stuck stale=1) must
    not be served forever. A shared bucket ("other") survives while any
    retained worker still maps to it."""
    monkeypatch.setenv("DBX_WORKER_LABEL_MAX", "16")
    tenancy.reset_tenant_buckets()
    reg = Registry()
    clock = [0.0]
    fv = fleet.FleetView(registry=reg, clock=lambda: clock[0],
                         stale_s_override=1.0)
    fv.update("w-keep", _frame(gen="k1", rate=1.0))
    fv.update("w-drop", _frame(gen="d1", rate=9.0))
    fv.collect(reg)
    assert 'worker="w-drop"' in reg.render_prometheus()
    fv.forget("w-drop")
    fv.collect(reg)
    text = reg.render_prometheus()
    assert 'worker="w-drop"' not in text
    assert 'worker="w-keep"' in text
    # The staleness EVICTION path retires series the same way.
    clock[0] += 10.0             # 3x the 1s bound -> prune evicts
    fv.update("w-late", _frame(gen="l1", t=2000.0))
    assert fv.prune() == ["w-keep"]
    fv.collect(reg)
    text = reg.render_prometheus()
    assert 'worker="w-keep"' not in text
    assert 'worker="w-late"' in text
    tenancy.reset_tenant_buckets()


# ---------------------------------------------------------------------------
# SLO burn windows
# ---------------------------------------------------------------------------

def test_slo_burn_windows_and_counter(monkeypatch):
    monkeypatch.setenv("DBX_FLEET_SLO_BURN", "0.1")
    reg = Registry()
    clock = [1000.0]
    fv = fleet.FleetView(registry=reg, clock=lambda: clock[0])
    for _ in range(8):
        fv.observe_slo(False)
    for _ in range(2):
        fv.observe_slo(True)
    snap = fv.snapshot(now=clock[0])
    for win in ("5m", "1h"):
        assert snap["fleet"]["slo"][win] == {
            "ok": 8, "breach": 2, "burn_rate": 0.2}
    fv.collect(reg)
    assert reg.counter("dbx_fleet_slo_burn_total",
                       window="5m").value == 1
    # Past the 5m window the fast-burn signal clears; 1h still burns.
    clock[0] += 400.0
    snap = fv.snapshot(now=clock[0])
    assert snap["fleet"]["slo"]["5m"]["breach"] == 0
    assert snap["fleet"]["slo"]["1h"]["breach"] == 2


# ---------------------------------------------------------------------------
# dbxtop + --url CLI surfaces
# ---------------------------------------------------------------------------

def test_dbxtop_render_and_url(tmp_path):
    """`dbxtop` end to end: a live dispatcher's /fleet.json scraped over
    HTTP renders the per-worker table with the fleet rollup header."""
    queue = JobQueue()
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=60.0),
                      results_dir=str(tmp_path / "results"))
    srv = DispatcherServer(disp, bind="localhost:0", prune_interval_s=5.0,
                           metrics_port=0,
                           metrics_host="127.0.0.1").start()
    try:
        disp.fleet.update("w-top", _frame(
            gen="t", stages=_stage_frame_stats([0.01] * 3)))
        url = f"http://127.0.0.1:{srv.metrics.port}"
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = fleet.main(["--url", url])
        assert rc == 0
        out = buf.getvalue()
        assert "w-top" in out
        assert "fleet: 1 live" in out
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = fleet.main(["--url", url + "/fleet.json",
                             "--format", "json"])
        assert rc == 0
        assert json.loads(buf.getvalue())["workers"]["w-top"]
    finally:
        srv.stop()


def test_timeline_and_dump_accept_url():
    """The round-15 satellite: obs.timeline / obs.dump point at a live
    /stats.json (the span ring rides it) without any log shipping."""
    from distributed_backtesting_exploration_tpu.obs import (
        dump as dump_mod, timeline as timeline_mod)

    tid = obs.new_trace_id()
    t0 = time.time() - 1
    obs.emit_span("job.queue_wait", t0, 0.4, trace_id=tid, job="u1")
    obs.emit_span("job.dispatch", t0 + 0.4, 0.1, trace_id=tid, job="u1",
                  worker="w-url")
    obs.emit_span("job", t0, 1.0, trace_id=tid, job="u1", worker="w-url")
    srv = obs.MetricsServer(0, bind="127.0.0.1").start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = timeline_mod.main(["--url", url])
        assert rc == 0
        assert "critical-path stage attribution" in buf.getvalue()
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = dump_mod.main(["--url", url + "/stats.json"])
        assert rc == 0
        assert "dbx_span_seconds" in buf.getvalue()
    finally:
        srv.stop()


def test_timeline_url_exits_2_on_zero_events():
    """A live endpoint with an empty span ring is a broken pipeline
    from the operator's seat — exit 2, like the zero-parseable-JSONL
    case."""
    from distributed_backtesting_exploration_tpu.obs import (
        timeline as timeline_mod)

    reg = Registry()
    srv = obs.MetricsServer(0, registry=reg, bind="127.0.0.1").start()
    try:
        # A registry-scoped server still serves the PROCESS span ring;
        # point at a snapshot with the ring stripped via a fresh ring.
        obs.configure_ring(0)
        rc = timeline_mod.main(
            ["--url", f"http://127.0.0.1:{srv.port}"])
        assert rc == 2
    finally:
        obs.configure_ring()
        srv.stop()


# ---------------------------------------------------------------------------
# Frame suppression (dirty bit + heartbeat + rate floor)
# ---------------------------------------------------------------------------

def test_frame_dirty_bit_heartbeat_and_remark(monkeypatch):
    monkeypatch.setenv("DBX_FLEET_FRAME_MIN_S", "0")
    monkeypatch.setenv("DBX_FLEET_HEARTBEAT_S", "100")
    state = {"jobs": 1}
    wt = fleet.WorkerTelemetry(
        "w", stats_fn=lambda: {"jobs_completed": state["jobs"]},
        registry=Registry())
    now = 1000.0
    first = wt.take_frame_json(now)
    assert first
    # Clean poll inside the heartbeat: zero wire cost.
    assert wt.take_frame_json(now + 1) == ""
    # Change -> dirty -> frame.
    state["jobs"] = 2
    assert wt.take_frame_json(now + 2)
    # Clean again, but the heartbeat elapsed -> frame anyway.
    assert wt.take_frame_json(now + 200)
    # RPC failure path: remark resends the same content.
    assert wt.take_frame_json(now + 201) == ""
    wt.remark_dirty()
    assert wt.take_frame_json(now + 202)


def test_frame_rate_floor_suppresses_saturated_polls(monkeypatch):
    monkeypatch.setenv("DBX_FLEET_FRAME_MIN_S", "0.5")
    state = {"jobs": 0}

    def stats():
        state["jobs"] += 32     # saturated: dirty on every poll
        return {"jobs_completed": state["jobs"]}

    wt = fleet.WorkerTelemetry("w", stats_fn=stats, registry=Registry())
    now = 1000.0
    sent = sum(1 for i in range(100)
               if wt.take_frame_json(now + i * 0.01))
    assert sent <= 3            # ~1s of 10ms polls, 0.5s floor


# ---------------------------------------------------------------------------
# Lockdep gate: the gossip/merge paths under instrumented locks
# ---------------------------------------------------------------------------

def test_fleet_gossip_under_lockdep_is_violation_free(tmp_path,
                                                      monkeypatch):
    """The race-harness gate for the new paths (the test_serve twin):
    real workers gossip frames over gRPC into the FleetView while
    snapshots/scrapes read it — with every package lock instrumented.
    Zero violations pins the contract: no frame parse, JSON build or
    HTTP work happens under the view's lock."""
    from distributed_backtesting_exploration_tpu.analysis import lockdep

    monkeypatch.setenv("DBX_FLEET_FRAME_MIN_S", "0.02")
    monkeypatch.setenv("DBX_FLEET_HEARTBEAT_S", "0.05")
    was_active = lockdep.active()
    lockdep.install()
    lockdep.reset()
    try:
        queue = JobQueue()
        disp = Dispatcher(queue, PeerRegistry(prune_window_s=60.0),
                          results_dir=str(tmp_path / "results"))
        assert isinstance(disp.fleet._lock, lockdep._LockdepLock)
        srv = DispatcherServer(disp, bind="localhost:0",
                               prune_interval_s=0.1).start()
        worker = Worker(f"localhost:{srv.port}", compute.InstantBackend(),
                        worker_id="ld-0", poll_interval_s=0.02,
                        status_interval_s=0.5, jobs_per_chip=8)
        wt = threading.Thread(target=worker.run, daemon=True)
        try:
            wt.start()
            for rec in synthetic_jobs(24, 32, "sma_crossover", GRID,
                                      seed=9):
                queue.enqueue(rec)
            _wait(lambda: queue.drained, msg="drain under lockdep")
            _wait(lambda: "ld-0" in disp.fleet.snapshot()["workers"],
                  msg="frame merged under lockdep")
            # Concurrent readers: snapshot + full scrape while polls
            # still flow.
            for _ in range(5):
                disp.fleet.snapshot()
                disp.obs.render_prometheus()
                time.sleep(0.02)
        finally:
            worker.stop()
            wt.join(timeout=20)
            srv.stop()
        rep = lockdep.report()
        assert rep["violations"] == [], rep["violations"]
        # Non-vacuous: the view's lock was actually exercised.
        assert any("FleetView" in cls for cls in rep["held"]), rep["held"]
    finally:
        if not was_active:
            lockdep.uninstall()
        lockdep.reset()
