"""dbxlint rule tests: every rule demonstrated against a seeded fixture
violation (exact file, line, rule id), plus suppression semantics and the
CLI contract. The package-lints-clean gate lives in test_lint_clean.py."""

import importlib.util
import json
import os

import numpy as np
import pytest

from distributed_backtesting_exploration_tpu.analysis import (
    ast_rules, core, jaxpr_rules, lint as lint_cli, locks, proto_rules)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")


def _fixture_line(name: str, marker: str) -> int:
    """1-indexed line of the first source line containing ``marker``."""
    with open(os.path.join(FIXTURES, name)) as fh:
        for i, line in enumerate(fh, 1):
            if marker in line:
                return i
    raise AssertionError(f"marker {marker!r} not in {name}")


def _lint_fixture(name: str, rule):
    findings, suppressed, _ = core.lint_path(
        os.path.join(FIXTURES, name), [rule])
    return findings, suppressed


# ---------------------------------------------------------------------------
# One test per rule: exactly the planted finding
# ---------------------------------------------------------------------------

def test_trace_time_env_detects_pre_pr1_lanes_cap_pattern():
    """The regression fixture reproduces the pre-PR-1 ops/fused.py shape
    (DBX_LANES_CAP read inside a helper called from a jitted kernel
    launcher) and the round-11 twin (a DBX_SCHEDULE_DIR registry lookup
    reachable from a traced root — schedule consultation must stay
    host-side). Exactly those reads are flagged; the host-side reads
    (DBX_HOST_ONLY, DBX_AUTOTUNE) are not."""
    findings, _ = _lint_fixture("trace_time_env.py",
                                ast_rules.TraceTimeEnvRule())
    assert sorted((f.rule, f.path, f.line) for f in findings) == sorted([
        ("trace-time-env", "trace_time_env.py",
         _fixture_line("trace_time_env.py",
                       'os.environ.get("DBX_LANES_CAP")')),
        ("trace-time-env", "trace_time_env.py",
         _fixture_line("trace_time_env.py",
                       'os.environ.get("DBX_SCHEDULE_DIR", "")')),
    ])
    assert "static argument" in findings[0].message
    assert not any("DBX_AUTOTUNE" in f.message or "DBX_HOST_ONLY"
                   in f.message for f in findings)


def test_lock_discipline_flags_unlocked_mutation_only():
    findings, _ = _lint_fixture("lock_discipline.py",
                                locks.LockDisciplineRule())
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("lock-discipline", "lock_discipline.py",
         _fixture_line("lock_discipline.py", "self._pending.remove(item)"))]
    assert "_pending" in findings[0].message
    # `_done` is never mutated under the lock -> unguarded, not flagged.
    assert not any("_done" in f.message for f in findings)


def test_lock_discipline_interprocedural_proves_helpers_clean():
    """The PagePool `prepare()` shape: a private helper mutating guarded
    fields is CLEAN when every caller path holds the lock (previously
    only expressible as a suppression) — and still flagged when one
    reachable path (a public method, or a lock-free caller chain) does
    not."""
    findings, _ = _lint_fixture("lock_discipline_interproc.py",
                                locks.LockDisciplineRule())
    fname = "lock_discipline_interproc.py"
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("lock-discipline", fname,
         _fixture_line(fname, "self._slots.pop(key, None)")),
        ("lock-discipline", fname,
         _fixture_line(fname, "self._slots.clear()")),
    ]
    # The helper chain under prepare()'s lock is proven, not suppressed.
    assert not any(f.line == _fixture_line(fname, "self._slots[key] = slot")
                   for f in findings)
    assert not any(f.line == _fixture_line(fname,
                                           "self._free.extend(range(8))")
                   for f in findings)
    # The lock-free path is named in the interprocedural finding.
    sweep = next(f for f in findings
                 if f.line == _fixture_line(fname, "self._slots.clear()"))
    assert "audit" in sweep.message


def test_lock_discipline_covers_nested_classes(tmp_path):
    """A lock-owning class defined inside a function must not lint
    blind, and an inner class's `self._lock` must never be credited to
    the enclosing class's lock set."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import threading\n"
        "def factory():\n"
        "    class Inner:\n"
        "        def __init__(self):\n"
        "            self._lock = threading.Lock()\n"
        "            self._items = []\n"
        "        def ok(self, x):\n"
        "            with self._lock:\n"
        "                self._items.append(x)\n"
        "        def bad(self, x):\n"
        "            self._items.append(x)\n"
        "    return Inner\n"
        "class Outer:\n"
        "    def __init__(self):\n"
        "        self._free = []\n"
        "        class Helper:\n"
        "            def __init__(self):\n"
        "                self._lock = threading.Lock()\n"
        "        self.h = Helper()\n"
        "    def touch(self):\n"
        "        self._free.append(1)   # Outer owns NO lock: clean\n")
    findings, _, _ = core.lint_path(str(mod),
                                    [locks.LockDisciplineRule()])
    assert [(f.rule, f.line) for f in findings] == [("lock-discipline", 11)]
    assert "_items" in findings[0].message


def test_lock_order_detects_abba_cycle_and_self_nest():
    """The seeded 2-lock cycle reports BOTH inner acquisition sites (with
    the reverse site cross-referenced), the consistent-order hierarchy
    pair stays clean, and re-acquiring a held non-reentrant lock through
    a helper is a self-deadlock finding."""
    findings, _ = _lint_fixture("lock_order.py", locks.LockOrderRule())
    fname = "lock_order.py"
    ab = _fixture_line(fname, "VIOLATION: beta-under-alpha")
    ba = _fixture_line(fname, "VIOLATION: alpha-under-beta")
    nest = _fixture_line(fname, "VIOLATION: self-nest")
    sp = _fixture_line(fname, "VIOLATION: stats-under-pipeline")
    ps = _fixture_line(fname, "VIOLATION: pipeline-under-stats")
    assert sorted((f.rule, f.path, f.line) for f in findings) == sorted([
        ("lock-order", fname, ab),
        ("lock-order", fname, ba),
        ("lock-order", fname, nest),
        ("lock-order", fname, sp),
        ("lock-order", fname, ps),
    ])
    # The producer/consumer handoff ABBA (round 14) names both sides.
    handoff = next(f for f in findings if f.line == sp)
    assert "_pipeline" in handoff.message and "_stats" in handoff.message
    cyc = next(f for f in findings if f.line == ab)
    assert "cycle" in cyc.message and "_alpha" in cyc.message \
        and "_beta" in cyc.message
    assert f"lock_order.py:{ba}" in cyc.message   # reverse site named
    self_nest = next(f for f in findings if f.line == nest)
    assert "non-reentrant" in self_nest.message
    assert "reenter" in self_nest.message
    # The clean hierarchy never appears.
    assert not any("_inner" in f.message or "_outer" in f.message
                   for f in findings if f.line != nest)


def test_atomicity_flags_check_then_act_across_release():
    """Read under lock -> unlocked branch -> re-acquired write is the
    seeded violation; the double-checked and single-critical-section
    forms are clean."""
    findings, _ = _lint_fixture("atomicity.py", locks.AtomicityRule())
    fname = "atomicity.py"
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("atomicity", fname,
         _fixture_line(fname, "self._spent[tenant] = spent + cost"))]
    msg = findings[0].message
    assert "_spent" in msg and "stale" in msg and "re-validate" in msg
    # The clean twins: charge_checked (revalidated) and charge_atomic
    # (one critical section) must not be flagged — pinned by the single
    # finding assertion above (their writes are on different lines).


def test_lock_blocking_flags_device_sync_under_lock():
    """The PR-9 PagePool scrape-stall class as a rule: a device sync
    under the index lock is flagged; the same sync on a lock-free path
    is not (that is blocking-call's servicer variant, below). The
    round-14 producer/consumer case: a bounded handoff `put(timeout=)`
    under the producer's accounting lock is the same stall, while the
    consumer's lock-free `get(timeout=)` stays clean."""
    findings, _ = _lint_fixture("blocking_call.py",
                                locks.LockBlockingRule())
    fname = "blocking_call.py"
    sync = _fixture_line(fname, "jax.block_until_ready(page)")
    put = _fixture_line(fname, "self._q.put(item, timeout=1.0)")
    assert sorted((f.rule, f.path, f.line) for f in findings) == sorted([
        ("lock-blocking", fname, sync),
        ("lock-blocking", fname, put),
    ])
    by_line = {f.line: f for f in findings}
    assert "_lock" in by_line[sync].message
    assert "block_until_ready" in by_line[sync].message
    assert "put" in by_line[put].message
    assert "PipelineHandoff.submit" in by_line[put].message
    # The consumer's lock-free timeout'd get is not a finding.
    assert not any("collect" in f.message for f in findings)


def test_import_time_config_flags_module_level_env_and_io():
    findings, _ = _lint_fixture("import_time_config.py",
                                ast_rules.ImportTimeConfigRule())
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("import-time-config", "import_time_config.py",
         _fixture_line("import_time_config.py",
                       '_CAP = os.environ.get')),
        ("import-time-config", "import_time_config.py",
         _fixture_line("import_time_config.py", '_CONFIG = open')),
    ]


def test_blocking_call_flags_sleep_and_device_sync_in_servicer():
    findings, _ = _lint_fixture("blocking_call.py",
                                ast_rules.BlockingCallRule())
    fname = "blocking_call.py"
    sleep = _fixture_line(fname, "time.sleep(0.5)")
    sync = _fixture_line(fname, "jax.block_until_ready(request)")
    sub_get = _fixture_line(fname, "self._q.get(timeout=5.0)")
    run_get = _fixture_line(fname, "self._q.get(timeout=1.0)")
    assert sorted((f.rule, f.path, f.line) for f in findings) == sorted([
        ("blocking-call", fname, sleep),
        ("blocking-call", fname, sync),
        ("blocking-call", fname, sub_get),
        ("blocking-call", fname, run_get),
    ])
    by_line = {f.line: f for f in findings}
    assert "SlowDispatcher.RequestJobs" in by_line[sleep].message
    # Device-sync vocabulary (round 12): a handler blocking on the
    # accelerator is the same thread-pool theft as a sleep.
    assert "SlowDispatcher.GetStats" in by_line[sync].message
    # Timeout'd queue waits (round 14): flagged in a handler and on the
    # worker control thread; the allowlisted pipeline collector wait
    # (Worker._collect_loop) is clean.
    assert "SlowDispatcher.Subscribe" in by_line[sub_get].message
    assert "Worker.run" in by_line[run_get].message
    assert not any("_collect_loop" in f.message for f in findings)
    # StallingPool's under-lock sync belongs to lock-blocking, not here
    # (StallingPool is not a servicer / control-plane class).
    assert not any("StallingPool" in f.message for f in findings)
    assert not any("PipelineHandoff" in f.message for f in findings)


def test_obs_cardinality_flags_unbounded_label_values():
    """The seeded fixture plants a param-named id, a path, a peer address,
    an f-string built from a path, and a one-hop alias of an unbounded
    attribute (`wid = self.worker_id`) — all flagged; bounded literals and
    non-matching names are not, and the suppressed site counts as
    suppressed."""
    findings, suppressed = _lint_fixture("obs_cardinality.py",
                                         ast_rules.ObsCardinalityRule())
    assert suppressed == 1
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'worker=wid')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'job=job_id')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'file=path')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'peer=peer_addr')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'site=f"{path}')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'panel=panel_digest')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'tenant=tenant_id')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'shape=panel_key')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'stream=stream_key')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'sub=subscriber_id')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'worker=worker_id')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'worker=worker)')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'candidate=candidate')),
        ("obs-cardinality", "obs_cardinality.py",
         _fixture_line("obs_cardinality.py", 'regret=regret_s')),
    ]
    alias = findings[0]
    assert "wid = self.worker_id" in alias.message
    # Last binding wins in BOTH directions: `wid` above was first bound
    # to a literal, and `endpoint` (unbounded first, literal last) must
    # not be flagged.
    assert _fixture_line("obs_cardinality.py", "pool=endpoint") \
        not in [f.line for f in findings]
    assert not any("fx_ok_total" in f.message
                   or "fx_by_kernel_total" in f.message for f in findings)
    # Digest vocabulary (dispatch-by-digest round): content digests are
    # unbounded; the bounded cache-level label is not.
    assert not any("fx_cache_hits_total" in f.message for f in findings)
    # Tenant vocabulary (multi-tenant round): a RAW tenant id is
    # unbounded, but the bounded tenant-bucket map is a sanctioned
    # label source — both the direct call and its one-hop alias.
    tb_ok = _fixture_line("obs_cardinality.py", "tenant=tenant_bucket")
    tb_alias = _fixture_line("obs_cardinality.py", "tenant=bucket")
    assert tb_ok not in [f.line for f in findings]
    assert tb_alias not in [f.line for f in findings]
    # Shape-bucket vocabulary (autotuner round): a raw shape key is
    # unbounded; the clamped power-of-two shape_bucket rails are a
    # sanctioned label source.
    sb_ok = _fixture_line("obs_cardinality.py", "shape=shape_bucket")
    assert sb_ok not in [f.line for f in findings]
    # Stream vocabulary (live fan-out round): raw stream keys and
    # subscriber ids are unbounded; the bounded stream-bucket map is a
    # sanctioned label source.
    st_ok = _fixture_line("obs_cardinality.py", "stream=stream_bucket")
    assert st_ok not in [f.line for f in findings]
    # Worker vocabulary (fleet telemetry round): a raw worker id is
    # unbounded (one series per registration, forever); the bounded
    # worker-bucket map is a sanctioned label source.
    wb_ok = _fixture_line("obs_cardinality.py", "worker=worker_bucket")
    assert wb_ok not in [f.line for f in findings]
    # Decision-plane vocabulary (round 19): actual/candidate worker ids
    # and per-decision regret are unbounded runtime data; the bounded
    # route/outcome literals and the worker-bucket rails are not.
    assert not any("fx_decisions_ok_total" in f.message
                   or "fx_shadow_ok_total" in f.message for f in findings)
    dec_wb_ok = _fixture_line("obs_cardinality.py",
                              "worker=worker_bucket(worker))")
    assert dec_wb_ok not in [f.line for f in findings]


def test_obs_cardinality_ignores_splats_and_bounded_loops(tmp_path):
    """**label splats are opaque (judged at construction, not the splat)
    and loop variables over literal tuples don't match the unbounded
    vocabulary — the package's method=m / phase=phase idiom stays clean."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "def wire(reg, labels):\n"
        "    reg.gauge('fx_info', **labels).set(1)\n"
        "    hs = {m: reg.histogram('fx_rpc_seconds', method=m)\n"
        "          for m in ('RequestJobs', 'CompleteJobs')}\n"
        "    return hs\n")
    findings, _, _ = core.lint_path(str(mod),
                                    [ast_rules.ObsCardinalityRule()])
    assert findings == []


def _load_bad_kernels():
    spec = importlib.util.spec_from_file_location(
        "dbxlint_fixture_bad_kernel", os.path.join(FIXTURES, "bad_kernel.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_hygiene_flags_host_callback():
    mod = _load_bad_kernels()
    x = np.ones((4, 8), np.float32)
    findings = jaxpr_rules.check_traced(
        "cb", mod.kernel_with_callback, [x], path="bad_kernel.py", line=13)
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("kernel-hygiene", "bad_kernel.py", 13)]
    assert "pure_callback" in findings[0].message


def test_kernel_hygiene_flags_float64_leak():
    import jax

    mod = _load_bad_kernels()
    x = np.ones((4, 8), np.float32)
    with jax.enable_x64(True):
        findings = jaxpr_rules.check_traced(
            "f64", mod.kernel_with_f64, [x], path="bad_kernel.py", line=22)
    assert any("float64" in f.message for f in findings)
    assert all(f.rule == "kernel-hygiene" and f.path == "bad_kernel.py"
               and f.line == 22 for f in findings)


def test_kernel_hygiene_flags_weak_type_escape_and_passes_clean():
    mod = _load_bad_kernels()
    x = np.ones((4, 8), np.float32)
    weak = jaxpr_rules.check_traced("weak", mod.kernel_weak_output, [x])
    assert len(weak) == 1 and "weakly typed" in weak[0].message
    assert jaxpr_rules.check_traced("clean", mod.kernel_clean, [x]) == []


def test_kernel_hygiene_unknown_axis_is_a_finding_not_a_crash(monkeypatch):
    """A newly registered fused kernel with a grid axis/field the rule has
    no tiny-input template for must surface as a loud finding (telling the
    maintainer to extend the template), never crash the lint run."""
    from distributed_backtesting_exploration_tpu.rpc import compute

    spec = compute._FusedSpec({"threshold"}, ("threshold",),
                              lambda *a, **k: None)
    monkeypatch.setattr(compute.JaxSweepBackend, "_FUSED_STRATEGIES",
                        {"novel_strategy": spec})
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(
        ast_rules.__file__)))
    ctx = core.load_context(pkg)
    findings = jaxpr_rules.KernelHygieneRule().check(ctx)
    # TWO loud findings, one per coverage surface: the dense tiny-input
    # template gap AND the paged-path probe gap (a registry entry must
    # not silently skip the round-10 paged variants either). Each is
    # reported once (scan pass), never per substrate.
    assert len(findings) == 2
    assert all(f.rule == "kernel-hygiene"
               and "novel_strategy" in f.message for f in findings)
    assert any("'threshold'" in f.message for f in findings)
    assert any("paged" in f.message for f in findings)


def test_kernel_hygiene_skip_is_reported_not_clean_coverage():
    """Outside the package the kernel registry cannot be traced: the rule
    must land in rules_skipped, never in `rules` (skipped != clean)."""
    result = lint_cli.run([FIXTURES], core.all_rules())
    assert "kernel-hygiene" in result["rules_skipped"]
    assert "kernel-hygiene" not in result["rules"]
    assert "trace-time-env" in result["rules"]


def test_proto_drift_detects_planted_divergences():
    """Drifted copy of the real contract vs the real pb2 descriptor: a
    renumbered field, a renamed field (missing+extra pair), and a field
    the descriptor lacks — nothing else."""
    from distributed_backtesting_exploration_tpu.rpc import backtesting_pb2

    path = os.path.join(FIXTURES, "proto_drift", "drifted.proto")
    with open(path) as fh:
        text = fh.read()
    model = proto_rules.parse_proto_text(text)
    pb2_model = proto_rules.describe_pb2(backtesting_pb2)
    findings = proto_rules.diff_models(model, pb2_model,
                                       path="drifted.proto")

    def line_of(marker):
        for i, line in enumerate(text.splitlines(), 1):
            if marker in line:
                return i
        raise AssertionError(marker)

    assert len(findings) == 4, [f.message for f in findings]
    renum = next(f for f in findings
                 if "CompleteItem.elapsed_s" in f.message)
    assert "number 4" in renum.message and "3 in the pb2" in renum.message
    assert renum.line == line_of("DRIFT: pb2 has number 3")
    missing = next(f for f in findings if "Ack.details" in f.message)
    assert "missing from the pb2" in missing.message
    assert missing.line == line_of("string details = 2;")
    extra = next(f for f in findings
                 if "`Ack.detail`" in f.message)
    assert "does not declare" in extra.message
    prio = next(f for f in findings if "JobsRequest.priority" in f.message)
    assert "missing from the pb2" in prio.message
    assert prio.line == line_of("int32 priority = 4;")


def test_proto_parser_survives_oneof_and_nested_blocks():
    """A `oneof`'s closing brace must pop only its own frame: its fields
    attribute to the enclosing message (descriptor semantics) and fields
    declared AFTER it are not lost."""
    model = proto_rules.parse_proto_text(
        "message M {\n"
        "  int32 a = 1;\n"
        "  oneof kind {\n"
        "    int32 b = 2;\n"
        "  }\n"
        "  int32 c = 3;\n"
        "}\n"
        "message N { int32 d = 1; }\n")
    assert model.messages == {"M": {"a": 1, "b": 2, "c": 3},
                              "N": {"d": 1}}


def test_proto_drift_real_contract_is_clean():
    from distributed_backtesting_exploration_tpu.rpc import backtesting_pb2

    proto = os.path.join(
        os.path.dirname(FIXTURES), "..", "..",
        "distributed_backtesting_exploration_tpu", "rpc",
        "backtesting.proto")
    with open(proto) as fh:
        model = proto_rules.parse_proto_text(fh.read())
    assert proto_rules.diff_models(
        model, proto_rules.describe_pb2(backtesting_pb2),
        path="backtesting.proto") == []
    # Sanity that the parser actually saw the contract (not vacuous).
    assert "JobSpec" in model.messages
    assert model.messages["JobSpec"]["best_returns"] == 13
    assert model.services["Dispatcher"]["GetStats"] == ("StatsRequest",
                                                        "StatsReply")


# ---------------------------------------------------------------------------
# Suppressions + CLI
# ---------------------------------------------------------------------------

def test_suppression_respected_same_line_and_line_above():
    findings, suppressed = _lint_fixture("suppressed.py",
                                         ast_rules.ImportTimeConfigRule())
    # _A (same-line) and _B (line-above) suppressed; _C names the wrong
    # rule so its finding survives.
    assert suppressed == 2
    assert [(f.rule, f.line) for f in findings] == [
        ("import-time-config",
         _fixture_line("suppressed.py", "DBX_SUP_C"))]


def test_suppression_directive_inside_string_literal_does_not_count(tmp_path):
    """A directive appearing in a STRING VALUE (docs, error messages) must
    not silence findings — only real comment tokens do."""
    mod = tmp_path / "m.py"
    mod.write_text(
        'import os\n'
        '_X = os.environ.get("A", "see dbxlint: disable=all in docs")\n')
    findings, suppressed = core.lint_path(
        str(mod), [ast_rules.ImportTimeConfigRule()])[:2]
    assert suppressed == 0
    assert [(f.rule, f.line) for f in findings] == [("import-time-config", 2)]


def test_import_time_config_flags_attribute_form_io(tmp_path):
    """Network IO at import is spelled as attributes (socket.create_connection,
    urllib.request.urlopen) — the rule must match terminal names, not just
    bare `open`."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import socket\n"
        '_CONN = socket.create_connection(("localhost", 1))\n')
    findings, _, _ = core.lint_path(str(mod),
                                    [ast_rules.ImportTimeConfigRule()])
    assert [(f.rule, f.line) for f in findings] == [("import-time-config", 2)]
    assert "create_connection" in findings[0].message


def test_blocking_call_allowlist_is_sleep_only(tmp_path):
    """The Worker.run allowlist sanctions the poll-tick SLEEP only: any
    other blocking call added to an allowlisted method is still flagged."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import subprocess, time\n"
        "class Worker:\n"
        "    def run(self):\n"
        "        time.sleep(0.05)          # sanctioned poll tick\n"
        "        subprocess.run(['x'])     # NOT sanctioned\n")
    findings, _, _ = core.lint_path(str(mod), [ast_rules.BlockingCallRule()])
    assert [(f.rule, f.line) for f in findings] == [("blocking-call", 5)]
    assert "subprocess.run" in findings[0].message


def test_proto_drift_skipped_for_single_file_targets():
    """Single-file lint targets have no proto scan: proto-drift must land
    in rules_skipped, not claim clean coverage."""
    result = lint_cli.run([os.path.join(FIXTURES, "lock_discipline.py")],
                          core.all_rules())
    assert "proto-drift" in result["rules_skipped"]
    assert "kernel-hygiene" in result["rules_skipped"]
    assert "proto-drift" not in result["rules"]


def test_suppression_comma_space_list_and_justification_tail(tmp_path):
    """`disable=a, b -- why` (comma-space style) suppresses BOTH rules;
    prose after `--` never parses as a rule name."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import os\n"
        "# dbxlint: disable=import-time-config, trace-time-env -- test\n"
        '_A = os.environ.get("A")\n')
    findings, suppressed = core.lint_path(
        str(mod), [ast_rules.ImportTimeConfigRule()])[:2]
    assert findings == [] and suppressed == 1


def test_lock_discipline_ignores_local_shadow_of_guarded_global(tmp_path):
    """A function-local variable that shadows a guarded module global is
    local for the WHOLE function (Python scoping) — mutating it without
    the lock is not a violation."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import threading\n"
        "_lock = threading.Lock()\n"
        "_buf = []\n"
        "def guarded(x):\n"
        "    with _lock:\n"
        "        _buf.append(x)\n"
        "def shadow(x):\n"
        "    _buf = []\n"
        "    _buf.append(x)   # the LOCAL, not the guarded global\n"
        "def real_violation(x):\n"
        "    _buf.append(x)\n")
    findings, _, _ = core.lint_path(str(mod),
                                    [locks.LockDisciplineRule()])
    assert [(f.rule, f.line) for f in findings] == [("lock-discipline", 11)]


def test_cli_json_format_and_exit_codes(capsys, tmp_path):
    rc = lint_cli.main([os.path.join(FIXTURES, "import_time_config.py"),
                        "--rules", "import-time-config",
                        "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert not out["clean"]
    assert {f["rule"] for f in out["findings"]} == {"import-time-config"}
    assert out["rules"] == ["import-time-config"]

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    rc = lint_cli.main([str(clean), "--rules", "import-time-config"])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_cli_unknown_rule_errors():
    with pytest.raises(SystemExit):
        lint_cli.main(["--rules", "no-such-rule"])


def test_unparseable_file_is_loud(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    findings, _, ctx = core.lint_path(str(tmp_path), core.all_rules())
    assert findings == []
    assert len(ctx.skipped) == 1 and ctx.skipped[0][0] == "bad.py"
    result = lint_cli.run([str(tmp_path)], core.all_rules())
    assert not result["clean"]          # a syntax error never passes silently


def test_journal_discipline_flags_mutation_before_append():
    """The seeded fixture publishes into live state (records map, state
    FIFO, WFQ lane) BEFORE journaling the enqueue records — every
    journal-covered mutation above the append is flagged; the appends
    themselves and the payload staging above them are not."""
    findings, suppressed = _lint_fixture("journal_discipline.py",
                                         ast_rules.JournalDisciplineRule())
    assert suppressed == 0
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("journal-discipline", "journal_discipline.py",
         _fixture_line("journal_discipline.py",
                       "BUG: published before journaled")),
        ("journal-discipline", "journal_discipline.py",
         _fixture_line("journal_discipline.py", "._state.enqueue_n(")),
        ("journal-discipline", "journal_discipline.py",
         _fixture_line("journal_discipline.py", "._sched.push(")),
    ]
    assert "journal first, then publish" in findings[0].message
