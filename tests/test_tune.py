"""Substrate autotuner + schedule registry + fleet compile cache (tune/).

The round-11 contracts these tests pin:

- **Determinism**: the registry's serialization is a pure function of the
  measurements (same entries -> same bytes, journal and wire), and merge
  conflict resolution converges regardless of gossip order.
- **Persistence**: journal restore round-trips; a corrupt line is
  skipped AND counted, never fatal.
- **Precedence**: for EVERY substrate knob, explicit arg > env > tuned
  schedule > hardcoded default — an env override always beats a tuned
  schedule, and an invalid tuned value silently degrades to the default
  (tuning must never fail a job).
- **Numerics**: a tuned substrate flip can never change positions — the
  epilogue substrate contract of test_z_epilogue holds when the flip
  arrives via a tuned schedule instead of an arg/env knob.
- **Fleet exchange**: schedule entries gossip worker -> dispatcher ->
  worker over the real in-process gRPC loop, and a cold worker's compile
  cache installs a peer's entry byte-identically
  (dbx_compile_cache_hits_total{source="fleet"} > 0).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from distributed_backtesting_exploration_tpu import obs, tune
from distributed_backtesting_exploration_tpu.ops import fused
from distributed_backtesting_exploration_tpu.rpc import (
    backtesting_pb2 as pb, compute, service, wire)
from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
    Dispatcher, DispatcherServer, JobQueue, PeerRegistry, parse_grid,
    synthetic_jobs)
from distributed_backtesting_exploration_tpu.rpc.worker import Worker
from distributed_backtesting_exploration_tpu.tune import registry as treg


def _wait(pred, timeout=20.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------

def test_shape_bucket_bounded_pow2_rails():
    assert tune.shape_bucket(1260, 2000) == "t2048_p2048"
    assert tune.shape_bucket(64, 1) == "t64_p128"
    assert tune.shape_bucket(1, 1) == "t64_p128"
    # Clamped: arbitrarily large shapes share the top rail (the label set
    # stays finite — the obs-cardinality contract).
    assert tune.shape_bucket(10**9, 10**9) == "t65536_p4096"
    all_buckets = {tune.shape_bucket(t, p)
                   for t in (1, 100, 5000, 10**7)
                   for p in (1, 300, 10**6)}
    assert len(all_buckets) <= len(treg._T_BUCKETS) * len(treg._P_BUCKETS)


# ---------------------------------------------------------------------------
# Registry: determinism, persistence, corruption, merge
# ---------------------------------------------------------------------------

def _entry_args(i=0):
    return dict(family="sma_crossover", bucket="t128_p128",
                platform="cpu",
                substrates={"epilogue": "scan:32", "lanes_cap": "256"},
                trials=3 + i, best_us=41.5)


def test_registry_same_measurements_same_bytes(tmp_path):
    """Tuned-schedule determinism: identical measurement results produce
    identical registry bytes — journal file AND wire JSON."""
    paths = [str(tmp_path / f"{i}" / "schedule.v1.jsonl") for i in (0, 1)]
    regs = [tune.ScheduleRegistry(p) for p in paths]
    for r in regs:
        r.record(**_entry_args())
        r.record("momentum", "t256_p128", "cpu",
                 {"epilogue": "scan:8"}, trials=2, best_us=10.0)
    blobs = [open(p, "rb").read() for p in paths]
    assert blobs[0] == blobs[1]
    assert regs[0].to_json() == regs[1].to_json()
    # Re-recording the identical winner appends nothing (journal stays
    # byte-stable across re-tunes that reach the same answer).
    assert regs[0].record(**_entry_args()) is False
    assert open(paths[0], "rb").read() == blobs[0]


def test_registry_persistence_restore_and_corrupt_skip(tmp_path):
    path = str(tmp_path / "schedule.v1.jsonl")
    r = tune.ScheduleRegistry(path)
    r.record(**_entry_args())
    # Plant a torn/corrupt line plus schema garbage between valid ones.
    with open(path, "a") as fh:
        fh.write('{"truncated": \n')
        fh.write('"not an object"\n')
        fh.write(json.dumps({"v": 99, "family": "x", "bucket": "b",
                             "platform": "cpu",
                             "substrates": {"epilogue": "scan"}}) + "\n")
    r.record("momentum", "t256_p128", "cpu", {"epilogue": "ladder"},
             trials=1)
    r2 = tune.ScheduleRegistry(path)
    assert len(r2) == 2
    assert r2.corrupt_entries == 3           # skip-and-count, never fatal
    assert r2.lookup("sma_crossover", "t128_p128", "cpu") == {
        "epilogue": "scan:32", "lanes_cap": "256"}
    assert r2.lookup("momentum", "t256_p128", "cpu") == {
        "epilogue": "ladder"}
    # Unknown substrate keys are scrubbed on the way in (forward compat).
    r2.record("rsi", "t128_p128", "cpu",
              {"epilogue": "scan:8", "warp_drive": "on"}, trials=1)
    assert r2.lookup("rsi", "t128_p128", "cpu") == {"epilogue": "scan:8"}
    # An unwritable registry path degrades to memory-only (io_errors
    # counted, nothing raises — tuning never fails a job).
    (tmp_path / "blockfile").write_bytes(b"")
    blocked = tune.ScheduleRegistry(
        str(tmp_path / "blockfile" / "x.jsonl"))
    blocked.record(**_entry_args())
    assert blocked.lookup("sma_crossover", "t128_p128", "cpu") is not None
    assert blocked.io_errors >= 1


def test_registry_journal_write_never_holds_the_lookup_lock(tmp_path):
    """Round-12 lock-blocking fix: the journal append used to run under
    ``_lock``, stalling every lookup() on the worker submit hot path and
    every gossip merge for the write's duration (an NFS pause froze the
    whole resolution chain). Appends now drain through the pending-IO
    queue OUTSIDE it — this pins the contract: the file write happens
    with ``_lock`` free."""
    path = str(tmp_path / "schedule.v1.jsonl")
    reg = tune.ScheduleRegistry(path)
    lock_states = []
    real_open = open

    class SpyFile:
        def __init__(self, fh):
            self._fh = fh

        def write(self, s):
            lock_states.append((reg._lock.locked(),
                                reg._io_lock.locked()))
            return self._fh.write(s)

        def __getattr__(self, name):
            return getattr(self._fh, name)

    def spy_open(*a, **k):
        fh = real_open(*a, **k)
        if a and str(a[0]).endswith("schedule.v1.jsonl") and "a" in str(
                k.get("mode", a[1] if len(a) > 1 else "")):
            return SpyFile(fh)
        return fh

    import builtins

    orig = builtins.open
    builtins.open = spy_open
    try:
        assert reg.record(**_entry_args())
        assert reg.record("momentum", "t256_p128", "cpu",
                          {"epilogue": "ladder"}, trials=1)
    finally:
        builtins.open = orig
    assert lock_states, "no journal write observed"
    assert not any(main for main, _io in lock_states), \
        "journal write ran while the registry lock was held"
    assert all(_io for _main, _io in lock_states), \
        "journal writes must be serialized by the io lock"
    # And the journal still restores everything recorded.
    assert len(tune.ScheduleRegistry(path)) == 2


def test_registry_concurrent_records_restore_to_memory_state(tmp_path):
    """Journal order == mutation order even with the IO outside the lock
    (entries enqueue under ``_lock`` in mutation order; the io-lock
    holder drains sequentially): hammering record() from four threads
    must restore, via later-wins replay, to exactly the final in-memory
    entry for every key."""
    path = str(tmp_path / "schedule.v1.jsonl")
    reg = tune.ScheduleRegistry(path)

    def hammer(tid):
        for n in range(25):
            reg.record("sma_crossover", "t128_p128", "cpu",
                       {"epilogue": f"scan:{8 << (n % 3)}",
                        "lanes_cap": str(64 * (tid + 1))},
                       trials=tid * 100 + n)

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    restored = tune.ScheduleRegistry(path)
    assert len(restored) == len(reg) == 1
    assert restored.lookup("sma_crossover", "t128_p128", "cpu") == \
        reg.lookup("sma_crossover", "t128_p128", "cpu")


def test_registry_merge_is_order_independent():
    """Deterministic conflict resolution: more trials wins, ties resolve
    by canonical line order — both peers converge either way."""
    a = tune.ScheduleRegistry()
    b = tune.ScheduleRegistry()
    e_low = dict(_entry_args(), substrates={"epilogue": "ladder"},
                 trials=1)
    e_high = dict(_entry_args(), substrates={"epilogue": "scan:8"},
                  trials=9)
    a.record(**e_low)
    b.record(**e_high)
    payload_a, payload_b = a.to_json(), b.to_json()
    assert a.merge_json(payload_b) == 1
    assert b.merge_json(payload_a) == 0       # fewer trials: rejected
    assert a.to_json() == b.to_json()
    assert a.lookup("sma_crossover", "t128_p128", "cpu") == {
        "epilogue": "scan:8"}
    # Malformed payloads teach nothing and are counted.
    before = a.corrupt_entries
    assert a.merge_json("{nope") == 0
    assert a.merge_json(json.dumps([{"v": 1, "family": 7}])) == 0
    assert a.corrupt_entries == before + 2


def test_registry_dirty_tracking_and_remark():
    r = tune.ScheduleRegistry()
    r.record(**_entry_args())
    payload = r.take_dirty_json()
    assert json.loads(payload)[0]["family"] == "sma_crossover"
    assert r.take_dirty_json() == ""          # clean poll: zero bytes
    r.remark_dirty(payload)                   # lost-poll retry path
    assert r.take_dirty_json() == payload
    # Fleet-adopted entries (mark_dirty=False) do NOT echo back out.
    r.merge_json(json.dumps([dict(
        v=1, family="rsi", bucket="t128_p128", platform="cpu",
        substrates={"epilogue": "scan:8"}, trials=5, best_us=None)]))
    assert r.take_dirty_json() == ""


# ---------------------------------------------------------------------------
# Precedence: explicit arg > env > tuned schedule > default, per knob
# ---------------------------------------------------------------------------

def test_env_beats_tuned_schedule_every_knob(monkeypatch):
    sched = {"epilogue": "scan:32", "lanes_cap": "256",
             "table_sma": "hbm", "page_bars": "256"}
    with fused.tuned_schedule(sched):
        # Tuned beats default...
        assert fused._resolve_epilogue(None) == "scan:32"
        assert fused.resolve_lanes_cap() == 256
        assert fused._family_table("sma", None) == "hbm"
        assert fused.resolve_page_bars() == 256
        # ...env beats tuned...
        monkeypatch.setenv("DBX_EPILOGUE", "scan:16")
        monkeypatch.setenv("DBX_LANES_CAP", "512")
        monkeypatch.setenv("DBX_SMA_TABLE", "inline")
        monkeypatch.setenv("DBX_PAGE_BARS", "1024")
        assert fused._resolve_epilogue(None) == "scan:16"
        assert fused.resolve_lanes_cap() == 512
        assert fused._family_table("sma", None) == "inline"
        assert fused.resolve_page_bars() == 1024
        # ...and an explicit arg beats both.
        assert fused._resolve_epilogue("ladder") == "ladder"
        assert fused._family_table("sma", "hbm") == "hbm"
    # Outside the context nothing lingers.
    monkeypatch.delenv("DBX_EPILOGUE")
    assert fused._resolve_epilogue(None) == "scan"


def test_invalid_tuned_values_degrade_to_defaults():
    """A corrupt registry entry must NEVER fail a job: invalid tuned
    values fall through to today's hardcoded defaults, while the same
    strings via arg/env still raise (operator error stays loud)."""
    with fused.tuned_schedule({"epilogue": "warp", "lanes_cap": "100",
                               "table_sma": "vmem", "page_bars": "13"}):
        assert fused._resolve_epilogue(None) == "scan"
        assert fused.resolve_lanes_cap() == 0
        assert fused._family_table("sma", None) == "inline"
        assert fused.resolve_page_bars() == 512
    with pytest.raises(ValueError):
        fused._resolve_epilogue("warp")


def test_tuned_defaults_process_layer_below_thread_layer():
    fused.set_tuned_defaults({"page_bars": "1024", "epilogue": "ladder"})
    try:
        assert fused.resolve_page_bars() == 1024
        assert fused._resolve_epilogue(None) == "ladder"
        with fused.tuned_schedule({"epilogue": "scan:8"}):
            # Thread-local schedule wins for its keys; global fills rest.
            assert fused._resolve_epilogue(None) == "scan:8"
            assert fused.resolve_page_bars() == 1024
            assert fused.tuned_schedule_active() == {
                "page_bars": "1024", "epilogue": "scan:8"}
    finally:
        fused.set_tuned_defaults(None)
    assert fused._resolve_epilogue(None) == "scan"


def test_substrate_defaults_and_mesh_key_follow_tuned_schedule():
    """The mesh path's jit cache key folds substrate_defaults(): a tuned
    flip must change the key exactly like an env flip (the stale-compile
    bug class dbxlint trace-time-env exists for)."""
    base = fused.substrate_defaults()
    with fused.tuned_schedule({"epilogue": "scan:32",
                               "table_don": "inline"}):
        tuned = fused.substrate_defaults()
    assert tuned["epilogue"] == "scan:32" and base["epilogue"] == "scan"
    assert tuned["table_don"] == "inline" and base["table_don"] == "hbm"
    with fused.tuned_schedule({"epilogue": "scan:32"}):
        assert fused.route_substrates("sma_crossover")["epilogue"] \
            == "scan:32"


# ---------------------------------------------------------------------------
# Numerics: a tuned substrate flip never changes positions
# ---------------------------------------------------------------------------

def test_tuned_epilogue_flip_bit_identity_pin():
    """Reuses test_z_epilogue's parity harness: the scan-vs-ladder
    contract (positions bit-identical => position/sum metrics bit-exact,
    equity-path metrics within f32 association) must hold when the flip
    arrives via a TUNED SCHEDULE instead of an arg/env knob."""
    import test_z_epilogue as zep

    ohlcv = __import__(
        "distributed_backtesting_exploration_tpu.utils.data",
        fromlist=["data"]).synthetic_ohlcv(3, 84, seed=31)
    close = np.asarray(ohlcv.close, np.float32)
    fast = np.asarray([3.0, 5.0], np.float32)
    slow = np.asarray([10.0, 14.0], np.float32)

    def run(substrate):
        with fused.tuned_schedule({"epilogue": substrate}):
            return fused.fused_sma_sweep(close, fast, slow, cost=1e-3)

    zep._assert_substrate_parity(run, "tuned_sma_flip")


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

def test_autotune_off_by_default(monkeypatch):
    monkeypatch.delenv("DBX_AUTOTUNE", raising=False)
    tuner = tune.Autotuner(tune.ScheduleRegistry())
    assert tune.autotune_mode() == "off"
    assert tuner.tune("sma_crossover", "t128_p128", "cpu",
                      n_bars=96, n_combos=8) is None


def test_autotune_model_mode_is_deterministic(monkeypatch):
    monkeypatch.setenv("DBX_AUTOTUNE", "model")
    winners = []
    for _ in range(2):
        reg = tune.ScheduleRegistry()
        tuner = tune.Autotuner(reg)
        w = tuner.tune("sma_crossover", "t128_p128", "cpu",
                       n_bars=96, n_combos=8)
        winners.append(w)
        assert reg.lookup("sma_crossover", "t128_p128", "cpu") == w
    assert winners[0] == winners[1]
    # The model prefers the blocked scan over the full-T ladder (PR 3's
    # measured direction) — the prior must not invert it.
    assert winners[0]["epilogue"].startswith("scan")


def test_autotune_measure_mode_ranks_by_measurement(monkeypatch):
    monkeypatch.setenv("DBX_AUTOTUNE", "1")
    monkeypatch.setenv("DBX_AUTOTUNE_TRIALS", "64")   # measure everything
    reg = tune.ScheduleRegistry()
    tuner = tune.Autotuner(reg)
    calls = []

    def measure(substrates):
        calls.append(dict(substrates))
        if substrates.get("lanes_cap") == "256":
            raise RuntimeError("candidate blew VMEM")   # not the winner
        return 0.001 if substrates["epilogue"] == "ladder" else 0.01

    w = tuner.tune("momentum", "t128_p128", "cpu", n_bars=96,
                   n_combos=8, measure=measure)
    # Measurement overrides the model prior (which prefers scan).
    assert w["epilogue"] == "ladder"
    assert w["lanes_cap"] != "256"            # failing candidates skipped
    e = reg.entries()[0]
    assert e["trials"] == len(calls) - sum(
        1 for c in calls if c.get("lanes_cap") == "256")
    assert e["best_us"] == pytest.approx(1000.0)
    c = obs.get_registry().counter("dbx_autotune_trials_total",
                                   family="momentum")
    assert c.value >= e["trials"]


def test_autotune_prune_keeps_incumbent_and_epilogue_diversity():
    """The measured set always contains today's defaults (a tune can
    never regress past the incumbent) and at least one candidate per
    epilogue value (a chip-shaped prior must not prune the whole truth
    away on a platform where it is wrong)."""
    from distributed_backtesting_exploration_tpu.tune import autotune

    scored = sorted(
        tune.candidate_space("sma_crossover"),
        key=lambda c: (tune.modeled_cost("sma_crossover", c,
                                         n_bars=512, n_combos=16),
                       tune.entry_line(c)))
    pruned = tune.Autotuner._pruned("sma_crossover", scored, 4)
    assert pruned[0] == autotune.default_substrates("sma_crossover")
    assert {c["epilogue"] for c in pruned} >= {
        "scan", "scan:8", "scan:32", "scan:128", "ladder"}
    lines = [tune.entry_line(c) for c in pruned]
    assert len(lines) == len(set(lines))       # no duplicates


def test_autotune_measure_mode_cannot_regress_past_default(monkeypatch):
    """When every non-default candidate measures WORSE, the incumbent
    wins and the recorded schedule equals today's defaults."""
    from distributed_backtesting_exploration_tpu.tune import autotune

    monkeypatch.setenv("DBX_AUTOTUNE", "1")
    reg = tune.ScheduleRegistry()
    tuner = tune.Autotuner(reg)
    incumbent = autotune.default_substrates("sma_crossover")

    def measure(substrates):
        return 0.001 if substrates == incumbent else 0.5

    w = tuner.tune("sma_crossover", "t512_p128", "cpu", n_bars=512,
                   n_combos=16, measure=measure)
    assert w == incumbent


def test_autotune_env_pinned_axes_excluded(monkeypatch):
    """An env-pinned knob would make its candidates measure the SAME
    substrate (env beats tuned), so the axis is dropped from the search
    AND from the recorded schedule — a noise-picked value must never
    gossip fleet-wide as a measured winner."""
    monkeypatch.setenv("DBX_AUTOTUNE", "1")
    monkeypatch.setenv("DBX_EPILOGUE", "ladder")
    reg = tune.ScheduleRegistry()
    tuner = tune.Autotuner(reg)
    seen_keys = set()

    def measure(substrates):
        seen_keys.update(substrates)
        return 0.01

    w = tuner.tune("sma_crossover", "t128_p128", "cpu", n_bars=96,
                   n_combos=8, measure=measure)
    assert "epilogue" not in seen_keys
    assert "epilogue" not in w
    assert "epilogue" not in reg.entries()[0]["substrates"]
    # Everything pinned -> nothing to tune, no entry recorded
    # (stochastic has no table axis, so epilogue+lanes is its whole
    # search space).
    monkeypatch.setenv("DBX_LANES_CAP", "256")
    reg2 = tune.ScheduleRegistry()
    assert tune.Autotuner(reg2).tune(
        "stochastic", "t128_p128", "cpu", n_bars=96, n_combos=8,
        measure=measure) is None
    assert len(reg2) == 0


def test_cache_sync_remembers_foreign_rejections_and_unmark(tmp_path):
    """A foreign-tag entry is refused ONCE (missing() stops re-requesting
    it — a mixed-generation fleet must not re-download the foreign set
    every tick), and unmark() re-surfaces offers whose RPC was lost."""
    sync = tune.CacheSync(str(tmp_path / "c"), runtime_tag="t|cpu")
    foreign = [(tune.entry_key("f1", "OTHER|tpu"), "f1", b"x")]
    assert sync.install(foreign) == 0
    assert sync.missing([foreign[0][0]]) == []      # refusal remembered
    with open(os.path.join(str(tmp_path / "c"), "mine"), "wb") as fh:
        fh.write(b"m")
    offers = sync.poll_new()
    assert len(offers) == 1
    assert sync.poll_new() == []                    # marked seen
    sync.unmark(offers)                             # lost-offer retry
    assert sync.poll_new() == offers
    # Interrupted-install temp files are never scanned or offered.
    with open(os.path.join(str(tmp_path / "c"), ".dbx_fetch_x"),
              "wb") as fh:
        fh.write(b"partial")
    assert all(n != ".dbx_fetch_x" for _, n, _ in sync.poll_new())


def test_candidate_space_shape():
    sma = tune.candidate_space("sma_crossover")
    assert all("table_sma" in c for c in sma)
    assert {c["epilogue"] for c in sma} == {"scan:8", "scan:32",
                                            "scan:128", "ladder"}
    mom_paged = tune.candidate_space("momentum", paged=True)
    assert all("page_bars" in c for c in mom_paged)
    assert all("table_" not in k for c in tune.candidate_space("rsi")
               for k in c)


# ---------------------------------------------------------------------------
# Backend consultation at group-submit time
# ---------------------------------------------------------------------------

def _sma_specs(n=2, bars=96, seed=6):
    grid = parse_grid("fast=3:5,slow=10:14:2")
    jobs = synthetic_jobs(n, bars, "sma_crossover", grid, cost=1e-3,
                          seed=seed)
    return [pb.JobSpec(id=r.id, strategy=r.strategy, ohlcv=r.ohlcv,
                       grid=wire.grid_to_proto(r.grid), cost=r.cost,
                       periods_per_year=252) for r in jobs]


def test_backend_serves_tuned_schedule_and_env_still_wins(monkeypatch):
    """The registry-consultation layer at group-submit time: a seeded
    tuned entry routes the group's substrates (visible on the
    dbx_fused_substrate_total counter and the tuned info gauge), and an
    env knob set over it still wins — pinned end to end."""
    monkeypatch.delenv("DBX_AUTOTUNE", raising=False)
    backend = compute.JaxSweepBackend(use_fused=True)
    specs = _sma_specs()
    bucket = tune.shape_bucket(96, 6)
    backend.schedule_registry.record(
        "sma_crossover", bucket, backend._platform,
        {"epilogue": "scan:48"}, trials=1)
    reg = obs.get_registry()
    c_tuned = reg.counter("dbx_fused_substrate_total",
                          kernel="sma_crossover", epilogue="scan:48",
                          table="inline")
    before = c_tuned.value
    assert len(backend.process(specs)) == len(specs)
    assert c_tuned.value == before + 1
    g = reg.gauge("dbx_tuned_substrate_info", kernel="sma_crossover",
                  bucket=bucket, epilogue="scan:48", table="default",
                  lanes_cap="default", page_bars="default")
    assert g.value == 1
    # Env override beats the tuned schedule for the SAME group shape.
    monkeypatch.setenv("DBX_EPILOGUE", "ladder")
    c_env = reg.counter("dbx_fused_substrate_total",
                        kernel="sma_crossover", epilogue="ladder",
                        table="inline")
    env_before = c_env.value
    assert len(backend.process(_sma_specs(seed=7))) == 2
    assert c_env.value == env_before + 1
    assert c_tuned.value == before + 1        # tuned route NOT taken


def test_backend_autotune_first_contact_records_winner(monkeypatch):
    monkeypatch.setenv("DBX_AUTOTUNE", "model")
    backend = compute.JaxSweepBackend(use_fused=True)
    assert len(backend.schedule_registry) == 0
    backend.process(_sma_specs(seed=8))
    assert len(backend.schedule_registry) == 1
    e = backend.schedule_registry.entries()[0]
    assert e["family"] == "sma_crossover"
    assert e["platform"] == backend._platform
    # Second contact with the same bucket re-uses, never re-tunes.
    backend.process(_sma_specs(seed=9))
    assert len(backend.schedule_registry) == 1


# ---------------------------------------------------------------------------
# Compile cache: keys, sync accounting, store bounds
# ---------------------------------------------------------------------------

def test_entry_key_folds_runtime_tag():
    k1 = tune.entry_key("cachefile_abc", "0.4.37|cpu")
    assert k1 == tune.entry_key("cachefile_abc", "0.4.37|cpu")
    assert k1 != tune.entry_key("cachefile_abc", "0.4.38|cpu")
    assert k1 != tune.entry_key("cachefile_abc", "0.4.37|tpu")
    assert len(k1) == 32


def test_cache_sync_accounting_and_store(tmp_path):
    reg = obs.get_registry()

    def counter(kind, source):
        return reg.counter(f"dbx_compile_cache_{kind}_total",
                           source=source)

    d = str(tmp_path / "cache")
    os.makedirs(d)
    with open(os.path.join(d, "prewarm"), "wb") as fh:
        fh.write(b"P" * 8)
    base = {k: counter(*k).value
            for k in (("hits", "local"), ("misses", "local"),
                      ("hits", "fleet"), ("misses", "fleet"))}
    sync = tune.CacheSync(d, runtime_tag="t|cpu")
    assert counter("hits", "local").value == base[("hits", "local")] + 1
    assert sync.poll_new() == []              # prewarm is not re-offered
    with open(os.path.join(d, "compiled_x"), "wb") as fh:
        fh.write(b"X" * 16)
    offers = sync.poll_new()
    assert [(k, n) for k, n, _ in offers] == [
        (tune.entry_key("compiled_x", "t|cpu"), "compiled_x")]
    assert counter("misses", "local").value \
        == base[("misses", "local")] + 1

    store = tune.CompileStore(max_bytes=1 << 20)
    for k, n, payload in offers:
        assert store.offer(k, n, payload)
        assert not store.offer(k, n, payload)     # dup ignored
    assert store.stats()["entries"] == 1
    # A second, cold worker: fetch + install, bit-identical bytes.
    d2 = str(tmp_path / "cache2")
    sync2 = tune.CacheSync(d2, runtime_tag="t|cpu")
    miss = sync2.missing(store.keys())
    assert miss == store.keys()
    entries = [(k,) + store.get(k) for k in miss]
    assert sync2.install(entries) == 1
    assert open(os.path.join(d2, "compiled_x"), "rb").read() == b"X" * 16
    assert counter("hits", "fleet").value == base[("hits", "fleet")] + 1
    assert sync2.missing(store.keys()) == []
    # A peer on a different runtime tag is refused.
    sync3 = tune.CacheSync(str(tmp_path / "cache3"),
                           runtime_tag="OTHER|tpu")
    assert sync3.install(entries) == 0
    sync3.count_fleet_misses(1)
    assert counter("misses", "fleet").value \
        == base[("misses", "fleet")] + 1


def test_compile_store_byte_bound_evicts_lru():
    store = tune.CompileStore(max_bytes=40)
    assert store.offer("k1", "n1", b"a" * 30)
    assert store.offer("k2", "n2", b"b" * 30)   # evicts k1
    assert store.get("k1") is None
    assert store.get("k2") == ("n2", b"b" * 30)
    assert len(store.keys()) == 1
    assert not store.offer("k3", "n3", b"")     # empty payload refused


# ---------------------------------------------------------------------------
# Fleet round-trips over the in-process gRPC loop
# ---------------------------------------------------------------------------

class _TuneProbeBackend:
    """Instant completions + a schedule registry (so the worker's tune
    sync legs engage without paying jax compiles)."""

    chips = 1

    def __init__(self):
        self.schedule_registry = tune.ScheduleRegistry()

    def process(self, jobs):
        return [compute.Completion(j.id, b"", 0.0, trace_id=j.trace_id)
                for j in jobs]


def _server(queue, **kw):
    disp = Dispatcher(queue, PeerRegistry(prune_window_s=30.0), **kw)
    srv = DispatcherServer(disp, bind="localhost:0",
                           prune_interval_s=0.5).start()
    return disp, srv


def test_schedule_gossip_worker_to_fleet_to_worker():
    """Worker A tunes an entry; it rides JobsRequest.schedule_json into
    the dispatcher's fleet registry and worker B adopts it from GetStats
    — the Nth worker inherits the first worker's tuning."""
    queue = JobQueue()
    disp, srv = _server(queue)
    a, b = _TuneProbeBackend(), _TuneProbeBackend()
    a.schedule_registry.record("sma_crossover", "t128_p128", "cpu",
                               {"epilogue": "scan:32"}, trials=2,
                               best_us=7.0)
    workers, threads = [], []
    try:
        for backend in (a, b):
            w = Worker(f"localhost:{srv.port}", backend,
                       poll_interval_s=0.02, status_interval_s=0.05)
            w.tune_sync_interval_s = 0.05
            t = threading.Thread(target=lambda w=w: w.run(), daemon=True)
            t.start()
            workers.append(w)
            threads.append(t)
        _wait(lambda: len(disp.fleet_schedule) == 1,
              msg="fleet registry adopts worker A's entry")
        _wait(lambda: b.schedule_registry.lookup(
                  "sma_crossover", "t128_p128", "cpu") is not None,
              msg="worker B inherits the tuned schedule")
        assert b.schedule_registry.lookup(
            "sma_crossover", "t128_p128", "cpu") == {"epilogue": "scan:32"}
        # Adopted entries are not gossiped back as dirty.
        assert b.schedule_registry.take_dirty_json() == ""
    finally:
        for w in workers:
            w.stop()
        for t in threads:
            t.join(timeout=10)
        srv.stop()


def test_fleet_compile_cache_round_trip_over_grpc(tmp_path):
    """Worker B's cold start hits worker A's compile-cache entry through
    the real FetchCompiled/OfferCompiled RPCs: bytes install
    bit-identically and the fleet hit counter moves — the integration
    pin for dbx_compile_cache_hits_total{source="fleet"} > 0."""
    import grpc

    reg = obs.get_registry()
    hits = reg.counter("dbx_compile_cache_hits_total", source="fleet")
    before = hits.value
    queue = JobQueue()
    disp, srv = _server(queue)
    try:
        channel = grpc.insecure_channel(
            f"localhost:{srv.port}",
            options=service.default_channel_options())
        stub = service.DispatcherStub(channel)
        # Worker A: one entry its own compile just wrote.
        dir_a = str(tmp_path / "a")
        sync_a = tune.CacheSync(dir_a, runtime_tag="t|cpu")
        blob = os.urandom(512)
        with open(os.path.join(dir_a, "jitcache_deadbeef"), "wb") as fh:
            fh.write(blob)
        offers = sync_a.poll_new()
        stub.OfferCompiled(pb.CompiledOffer(
            worker_id="wa",
            entries=[pb.CompiledEntry(key=k, name=n, payload=p)
                     for k, n, p in offers]))
        assert disp.compile_store.stats()["entries"] == 1
        # Worker B: cold dir, listing -> fetch -> install.
        sync_b = tune.CacheSync(str(tmp_path / "b"), runtime_tag="t|cpu")
        listing = stub.FetchCompiled(pb.CompiledRequest(worker_id="wb"))
        assert not listing.entries            # listing carries keys only
        miss = sync_b.missing(listing.known_keys)
        assert len(miss) == 1
        got = stub.FetchCompiled(pb.CompiledRequest(worker_id="wb",
                                                    keys=miss))
        installed = sync_b.install(
            (e.key, e.name, e.payload) for e in got.entries)
        assert installed == 1
        assert open(os.path.join(str(tmp_path / "b"),
                                 "jitcache_deadbeef"), "rb").read() == blob
        assert hits.value == before + 1
        channel.close()
    finally:
        srv.stop()


def test_fleet_entry_hits_in_another_checkout_and_cache_dir(tmp_path):
    """A kernel compiled by a worker whose checkout and cache dir sit at
    one path, its entry carried by CacheSync into another worker's dir,
    is a jax persistent-cache hit there for the same kernel traced from
    a source file at another path. It was a miss while jax's XLA
    sub-caches were on: they fold the cache dir into every key."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from distributed_backtesting_exploration_tpu.tune import compile_cache

    src = "def kern(x):\n    return jnp.cumsum(jnp.sin(x) * 3.0 + x) - 1\n"
    options = ("jax_compilation_cache_dir",
               "jax_persistent_cache_enable_xla_caches",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")
    prior = {o: getattr(jax.config, o) for o in options}
    events = []

    def listener(event, **kw):
        events.append(event)

    def hits_compiling_in(checkout, cache_dir):
        ns = {"jnp": jnp}
        exec(compile(src, str(checkout / "kern.py"), "exec"), ns)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()
        events.clear()
        jax.jit(ns["kern"])(np.arange(8, dtype=np.float32))
        return events.count("/jax/compilation_cache/cache_hits")

    jax.monitoring.register_event_listener(listener)
    try:
        compile_cache.configure(min_compile_time_s=0.0)
        dirs = [str(tmp_path / c / ".jax_cache") for c in "ab"]
        sync_a, sync_b = (tune.CacheSync(d, runtime_tag="t|cpu")
                          for d in dirs)
        assert hits_compiling_in(tmp_path / "a", dirs[0]) == 0
        assert sync_b.install(sync_a.poll_new()) == 1
        assert hits_compiling_in(tmp_path / "b", dirs[1]) == 1
    finally:
        jax.monitoring.unregister_event_listener(listener)
        for option, value in prior.items():
            jax.config.update(option, value)
        compilation_cache.reset_cache()


def test_stats_reply_ships_fleet_schedule():
    import grpc

    queue = JobQueue()
    disp, srv = _server(queue)
    try:
        disp.fleet_schedule.record("rsi", "t256_p128", "cpu",
                                   {"epilogue": "scan:8"}, trials=4)
        channel = grpc.insecure_channel(
            f"localhost:{srv.port}",
            options=service.default_channel_options())
        stub = service.DispatcherStub(channel)
        reply = stub.GetStats(pb.StatsRequest())
        entries = json.loads(reply.schedule_json)
        assert [e["family"] for e in entries] == ["rsi"]
        channel.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# The one compile-cache directory resolver
# ---------------------------------------------------------------------------

def _record_config_updates(monkeypatch):
    import jax

    names = []
    real = jax.config.update

    def update(name, value):
        names.append(name)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return names


def test_compile_cache_env_dir_wins_and_nothing_else_is_set(monkeypatch,
                                                            tmp_path):
    from distributed_backtesting_exploration_tpu.tune import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    names = _record_config_updates(monkeypatch)
    assert compile_cache.default_cache_dir() == str(tmp_path)
    assert compile_cache.configure() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in names


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    from distributed_backtesting_exploration_tpu.tune import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    names = _record_config_updates(monkeypatch)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compile_cache.default_cache_dir() == want
    assert compile_cache.configure() == want
    assert "jax_compilation_cache_dir" in names
    assert jax.config.jax_compilation_cache_dir == want
