"""The obs layer: registry semantics, spans, rendering, endpoint, dump CLI,
distributed-trace plumbing, and the timeline analyzer."""

import json
import math
import threading
import urllib.request

import pytest

from distributed_backtesting_exploration_tpu import obs
from distributed_backtesting_exploration_tpu.obs import (
    dump, events, timeline)


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------

def test_counter_semantics():
    reg = obs.Registry()
    c = reg.counter("dbx_t_total", "help", method="A")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # get-or-create: same name+labels -> same child; new labels -> new child
    assert reg.counter("dbx_t_total", method="A") is c
    c2 = reg.counter("dbx_t_total", method="B")
    assert c2 is not c and c2.value == 0.0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_kind_and_name_validation():
    reg = obs.Registry()
    reg.counter("dbx_x_total")
    with pytest.raises(ValueError):
        reg.gauge("dbx_x_total")          # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("bad-name")           # invalid prometheus name
    with pytest.raises(ValueError):
        reg.counter("dbx_z_total", **{"0bad": 1})  # invalid label name


def test_gauge_set_fn_and_collector():
    reg = obs.Registry()
    g = reg.gauge("dbx_depth")
    g.set(4)
    assert g.value == 4
    reg.gauge_fn("dbx_live", lambda: 7)
    state = {"n": 0}
    reg.add_collector("c", lambda r: r.gauge("dbx_coll").set(
        state.__setitem__("n", state["n"] + 1) or state["n"]))
    snap = reg.snapshot()
    assert snap["dbx_live"]["values"][""] == 7
    assert snap["dbx_coll"]["values"][""] == 1
    reg.snapshot()
    assert state["n"] == 2                 # collector runs once per snapshot
    reg.remove_collector("c")
    reg.snapshot()
    assert state["n"] == 2


def test_histogram_buckets_and_summary():
    reg = obs.Registry()
    h = reg.histogram("dbx_lat_seconds", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.001, 0.005, 0.05, 5.0):
        h.observe(v)
    cum = dict(h.cumulative())
    # le is inclusive: the 0.001 observation lands in the 0.001 bucket
    assert cum[0.001] == 2 and cum[0.01] == 3 and cum[0.1] == 4
    assert cum[math.inf] == 5
    s = h.summary()
    assert s["count"] == 5 and s["max"] == 5.0
    assert s["sum"] == pytest.approx(5.0565)
    assert 0 < s["p50"] <= 0.01


def test_prometheus_rendering():
    reg = obs.Registry()
    reg.counter("dbx_c_total", "a counter", kind="x").inc(2)
    reg.gauge("dbx_g").set(1.5)
    reg.histogram("dbx_h_seconds", buckets=(0.1, 1.0)).observe(0.5)
    txt = reg.render_prometheus()
    assert "# TYPE dbx_c_total counter" in txt
    assert 'dbx_c_total{kind="x"} 2.0' in txt
    assert "dbx_g 1.5" in txt
    assert 'dbx_h_seconds_bucket{le="0.1"} 0' in txt
    assert 'dbx_h_seconds_bucket{le="1.0"} 1' in txt
    assert 'dbx_h_seconds_bucket{le="+Inf"} 1' in txt
    assert "dbx_h_seconds_count 1" in txt


def test_prometheus_escaping_hostile_label_and_help_values():
    """Backslash, double-quote, and newline in label values (and backslash/
    newline in HELP text) must be escaped per the text exposition format —
    emitted raw they terminate the sample line mid-value and the scrape
    fails to parse."""
    reg = obs.Registry()
    hostile = 'C:\\data\n"quoted"'
    reg.counter("dbx_esc_total", help="line one\nline two \\ backslash",
                path_kind=hostile).inc()
    txt = reg.render_prometheus()
    assert ('dbx_esc_total{path_kind='
            '"C:\\\\data\\n\\"quoted\\""} 1.0') in txt
    assert "# HELP dbx_esc_total line one\\nline two \\\\ backslash" in txt
    # No raw newline survives inside any line: every line is one sample
    # or one comment, never a torn continuation.
    for line in txt.splitlines():
        assert line.startswith(("#", "dbx_")), line


def test_registry_thread_safety():
    reg = obs.Registry()
    c = reg.counter("dbx_mt_total")
    h = reg.histogram("dbx_mt_seconds")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


# ---------------------------------------------------------------------------
# Spans + event log
# ---------------------------------------------------------------------------

def test_span_nesting_and_event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    events.configure(path)
    try:
        with obs.span("outer"):
            assert obs.current_span() == "outer"
            with obs.span("inner", jobs=3):
                assert obs.current_span() == "inner"
        assert obs.current_span() is None
    finally:
        events.configure(None)
    recs = [json.loads(ln) for ln in open(path)]
    inner = next(r for r in recs if r["name"] == "inner")
    outer = next(r for r in recs if r["name"] == "outer")
    assert inner["parent"] == "outer" and inner["jobs"] == 3
    assert outer["parent"] is None
    assert inner["dur_s"] <= outer["dur_s"]
    # span durations also land in the shared registry histogram
    s = obs.get_registry().summaries()
    assert s["dbx_span_seconds{span=inner}"]["count"] >= 1


def test_event_log_open_runs_outside_the_module_lock(tmp_path,
                                                     monkeypatch):
    """Round-12 lock-blocking fix: configure() and the DBX_OBS_JSONL
    first-use path used to open the file INSIDE the module lock — a
    slow open (NFS, a fifo) stalled every concurrent emit. Both opens
    now run with the lock free; a failed configure() leaves the
    previous log attached instead of half-torn-down."""
    path = str(tmp_path / "ev.jsonl")
    lock_states = []
    real_open = open

    def spy_open(*a, **k):
        if a and str(a[0]).endswith("ev.jsonl"):
            lock_states.append(events._lock.locked())
        return real_open(*a, **k)

    monkeypatch.setattr("builtins.open", spy_open)
    events.configure(path)
    try:
        assert lock_states == [False]
        # An unopenable reconfigure raises WITHOUT killing the live log.
        with pytest.raises(OSError):
            events.configure(str(tmp_path / "no" / "dir" / "x.jsonl"))
        assert events.enabled() and events.configured_path() == path
        events.emit("still_alive")
    finally:
        events.configure(None)
    assert "still_alive" in real_open(path).read()


def test_span_records_on_exception(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    events.configure(path)
    try:
        with pytest.raises(RuntimeError):
            with obs.span("boom"):
                raise RuntimeError("x")
    finally:
        events.configure(None)
    rec = json.loads(open(path).read().splitlines()[-1])
    assert rec["name"] == "boom" and rec["ok"] is False


def test_span_attrs_set_inside_the_block_reach_the_record():
    """A span hands its block the attrs dict: an attr known only inside
    (the size of the batch a wait returned) lands on the record."""
    with obs.span("attr_probe") as attrs:
        attrs["jobs"] = 7
    rec = next(r for r in reversed(obs.recent_spans())
               if r["name"] == "attr_probe")
    assert rec["jobs"] == 7


def _host_events(xplane_path, names):
    """``name -> [(start_ns, end_ns)]`` of the trace's host events."""
    from jax.profiler import ProfileData

    out = {n: [] for n in names}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_span_and_named_timer_are_host_events_in_a_profiler_trace(tmp_path):
    """While a jax.profiler trace runs, a span and a named timer are host
    events of their names on the profiler's clock, the inner one inside
    the outer one; the timer still observes only its own histogram."""
    import glob
    import os
    import time

    import jax

    reg = obs.Registry()
    hist = reg.histogram("dbx_probe_seconds")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("probe.outer"):
            time.sleep(0.005)
            with obs.timer(hist, name="probe.inner"):
                time.sleep(0.005)
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    ev = _host_events(path, ("probe.outer", "probe.inner"))
    assert len(ev["probe.outer"]) == 1 and len(ev["probe.inner"]) == 1
    (o0, o1), (i0, i1) = ev["probe.outer"][0], ev["probe.inner"][0]
    assert o0 <= i0 < i1 <= o1
    assert i1 - i0 >= 4e6 and o1 - o0 >= i1 - i0 + 8e6
    assert hist.summary()["count"] == 1
    # The named timer made no span record.
    assert not any(r["name"] == "probe.inner" for r in obs.recent_spans())
    # With the trace stopped, a span opens no annotation.
    from distributed_backtesting_exploration_tpu.obs import trace as obs_trace

    assert obs_trace._annotation_cls is jax.profiler.TraceAnnotation
    assert obs_trace._annotation("probe.after") is None


def test_obs_without_jax_annotates_nothing():
    """obs imports no jax, and in a process without jax a span and a
    named timer annotate nothing (a dispatcher-only process). The package
    root imports jax, so the child loads obs below a bare package."""
    import os
    import subprocess
    import sys

    import distributed_backtesting_exploration_tpu as dbx

    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('distributed_backtesting_exploration_tpu')\n"
        f"pkg.__path__ = [{os.path.dirname(dbx.__file__)!r}]\n"
        "sys.modules[pkg.__name__] = pkg\n"
        "from distributed_backtesting_exploration_tpu import obs\n"
        "from distributed_backtesting_exploration_tpu.obs import trace\n"
        "with obs.span('outer'):\n"
        "    with obs.timer(obs.get_registry().histogram("
        "'dbx_probe_seconds'), name='inner'):\n"
        "        pass\n"
        "loaded = [m for m in sys.modules\n"
        "          if m.split('.')[0] in ('jax', 'jaxlib')]\n"
        "assert not loaded, loaded\n"
        "assert trace._annotation_cls is None\n"
        "assert trace._annotation('x') is None\n"
        "print('ok', obs.get_registry().summaries()"
        "['dbx_span_seconds{span=outer}']['count'])\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["ok", "1"]


def test_span_trace_ids_context_and_ring(tmp_path):
    """Every span carries a (trace_id, span_id, parent_id) triple: nested
    spans parent locally, the outermost span of a trace_context adopts the
    remote parent, and completed spans land in the bounded ring with the
    same record the JSONL log gets."""
    path = str(tmp_path / "t.jsonl")
    events.configure(path)
    tid = obs.new_trace_id()
    try:
        with obs.trace_context(tid, parent_span_id="remote-parent"):
            assert obs.current_trace() == tid
            with obs.span("outer_t"):
                with obs.span("inner_t"):
                    pass
        assert obs.current_trace() is None
    finally:
        events.configure(None)
    recs = {r["name"]: r for r in map(json.loads, open(path))}
    outer, inner = recs["outer_t"], recs["inner_t"]
    assert outer["trace_id"] == inner["trace_id"] == tid
    assert outer["parent_id"] == "remote-parent"
    assert inner["parent_id"] == outer["span_id"]
    assert outer["span_id"] != inner["span_id"]
    assert "t0" in outer and "pid" in outer
    # The ring holds the same records (minus the writer-stamped ts/pid).
    ring = {r["name"]: r for r in obs.recent_spans()
            if r["name"] in ("outer_t", "inner_t")}
    assert ring["outer_t"]["span_id"] == outer["span_id"]

    # Multi-trace context (one batch, several jobs): spans carry a
    # `traces` pair list instead of a single trace_id.
    pairs = [(obs.new_trace_id(), "p1"), (obs.new_trace_id(), "p2")]
    with obs.trace_context(pairs):
        assert obs.current_trace() is None
        with obs.span("multi_t"):
            pass
    multi = next(r for r in reversed(obs.recent_spans())
                 if r["name"] == "multi_t")
    assert multi["traces"] == [list(p) for p in pairs]
    assert "trace_id" not in multi


def test_stats_payload_ships_recent_spans():
    from distributed_backtesting_exploration_tpu.obs import http as obs_http

    with obs.span("payload_probe"):
        pass
    reg = obs.Registry()
    payload = obs_http.stats_payload(reg)
    fam = payload["dbx_spans_recent"]
    assert fam["type"] == "spans"
    assert any(r["name"] == "payload_probe" for r in fam["values"])
    # dump's snapshot renderer must skip the spans family, not crash.
    assert "payload_probe" not in dump.render_snapshot(
        {"dbx_spans_recent": fam})


# ---------------------------------------------------------------------------
# HTTP endpoint + dump CLI (the tier-1 smoke of the tooling)
# ---------------------------------------------------------------------------

def test_metrics_endpoint_and_dump_cli(tmp_path, capsys):
    reg = obs.Registry()
    reg.counter("dbx_cli_total").inc(3)
    h = reg.histogram("dbx_cli_seconds")
    h.observe(0.01)
    srv = obs.start_metrics_server(0, registry=reg)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "dbx_cli_total 3.0" in body
        snap = json.loads(
            urllib.request.urlopen(base + "/stats.json").read())
        assert snap["dbx_cli_seconds"]["type"] == "histogram"
        # dump CLI against the live endpoint
        assert dump.main([base]) == 0
        out = capsys.readouterr().out
        assert "dbx_cli_seconds" in out and "dbx_cli_total" in out
    finally:
        srv.stop()

    # dump CLI against a JSONL event log
    path = str(tmp_path / "trace.jsonl")
    events.configure(path)
    try:
        with obs.span("phase_a"):
            with obs.span("phase_b"):
                pass
    finally:
        events.configure(None)
    assert dump.main([path]) == 0
    out = capsys.readouterr().out
    assert "phase_a" in out and "phase_a/phase_b" in out and "share" in out


def test_dump_and_timeline_cli_multi_input_malformed_and_empty(tmp_path,
                                                               capsys):
    """The CI/tooling contract of BOTH CLIs: several --jsonl inputs merge,
    malformed lines are skipped AND counted, and zero parseable events
    exits non-zero (a typo'd path must not render as a healthy quiet
    fleet)."""
    tid = obs.new_trace_id()
    a = tmp_path / "a.jsonl"
    b = tmp_path / "b.jsonl"
    a.write_text(
        json.dumps({"ev": "span", "name": "job.queue_wait", "t0": 10.0,
                    "dur_s": 1.0, "trace_id": tid, "span_id": "s1",
                    "job": "j1"}) + "\n"
        + json.dumps({"ev": "span", "name": "job", "t0": 10.0,
                      "dur_s": 4.0, "trace_id": tid, "span_id": "s0",
                      "job": "j1", "worker": "w0"}) + "\n"
        + "{torn line\n")
    b.write_text(
        json.dumps({"ev": "span", "name": "worker.process", "t0": 12.0,
                    "dur_s": 1.5, "trace_id": tid, "span_id": "s2",
                    "parent_id": "d1"}) + "\n"
        + "not json at all\n")

    rc = dump.main([str(a), "--jsonl", str(b)])
    out = capsys.readouterr()
    assert rc == 0
    assert "job.queue_wait" in out.out and "worker.process" in out.out
    assert "2 malformed line(s) skipped" in out.out

    rc = timeline.main(["--jsonl", str(a), str(b), "--format", "json"])
    out = capsys.readouterr()
    assert rc == 0
    assert "skipped 2 malformed line(s)" in out.err
    summary = json.loads(out.out)
    assert summary["jobs"] == 1
    job = summary["per_job"][0]
    assert job["job"] == "j1" and job["worker"] == "w0"
    # Critical path partitions the 4s e2e window: 1s queue-wait, 1.5s
    # execute (worker.process fallback), the rest transport.
    assert job["stages"]["queue_wait"] == pytest.approx(1.0)
    assert job["stages"]["execute"] == pytest.approx(1.5)
    assert job["stages"]["transport"] == pytest.approx(1.5)
    assert sum(job["stages"].values()) == pytest.approx(job["e2e_s"])

    # --job filter: a non-matching id exits non-zero.
    assert timeline.main(["--jsonl", str(a), "--job", "nope"]) == 2
    capsys.readouterr()

    # Zero parseable events -> non-zero exit for both CLIs.
    empty = tmp_path / "empty.jsonl"
    empty.write_text("garbage\n{more garbage\n")
    assert dump.main([str(empty)]) == 2
    assert timeline.main(["--jsonl", str(empty)]) == 2
    capsys.readouterr()


def test_timeline_straggler_flagging():
    """Jobs whose stage time exceeds the fleet p95 are flagged once the
    fleet is big enough; below min_straggler_jobs the p95 of a tiny
    sample flags nobody."""
    spans = []
    for i in range(10):
        tid = f"{i:032x}"
        dur = 5.0 if i == 9 else 1.0   # job 9: 5x the fleet's execute
        spans.append({"ev": "span", "name": "job", "t0": 0.0,
                      "dur_s": dur + 1.0, "trace_id": tid, "span_id": "r",
                      "job": f"job{i}", "worker": f"w{i % 2}"})
        spans.append({"ev": "span", "name": "worker.execute", "t0": 0.5,
                      "dur_s": dur, "trace_id": tid, "span_id": "e"})
    tls = timeline.reconstruct(spans)
    assert len(tls) == 10
    s = timeline.summarize(tls)
    flagged = {x["job"] for x in s["stragglers"]
               if x["stage"] == "execute"}
    assert flagged == {"job9"}
    # Per-worker attribution covers both workers.
    assert set(s["workers"]) == {"w0", "w1"}
    # A 3-job fleet flags nothing (p95 of a tiny sample is noise).
    tiny = timeline.reconstruct(spans[:6])
    assert timeline.summarize(tiny)["stragglers"] == []


def test_timeline_panel_cache_hit_pseudo_stage():
    """Dispatch-by-digest attribution: a decode span with a truthy
    `cache_hit` attr charges its window to the `panel_cache_hit`
    pseudo-stage (not decode, and never silently to transport); a d2h
    span's cache_hit flag is informational only — the result drain it
    times is real work and stays d2h. Stage seconds still sum exactly to
    the e2e window."""
    tid = obs.new_trace_id()
    spans = [
        {"ev": "span", "name": "job", "t0": 0.0, "dur_s": 4.0,
         "trace_id": tid, "span_id": "s0", "job": "j1", "worker": "w0"},
        {"ev": "span", "name": "job.queue_wait", "t0": 0.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s1", "job": "j1"},
        # The digest-cache hit window: decode span, cache_hit=True.
        {"ev": "span", "name": "worker.decode", "t0": 1.5, "dur_s": 0.5,
         "trace_id": tid, "span_id": "s2", "cache_hit": True,
         "cache_hits": 1},
        {"ev": "span", "name": "worker.execute", "t0": 2.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s3"},
        {"ev": "span", "name": "worker.d2h", "t0": 3.0, "dur_s": 0.5,
         "trace_id": tid, "span_id": "s4", "cache_hit": True},
    ]
    tls = timeline.reconstruct(spans)
    stages = timeline.critical_path(tls[tid])
    assert stages["panel_cache_hit"] == pytest.approx(0.5)
    assert stages["decode"] == 0.0
    assert stages["d2h"] == pytest.approx(0.5)   # drain stays d2h
    assert stages["execute"] == pytest.approx(1.0)
    assert sum(stages.values()) == pytest.approx(4.0)

    # Without the attr the same window is ordinary decode work.
    spans[2] = dict(spans[2], cache_hit=False)
    stages = timeline.critical_path(timeline.reconstruct(spans)[tid])
    assert stages["decode"] == pytest.approx(0.5)
    assert stages["panel_cache_hit"] == 0.0


def test_timeline_carry_hit_pseudo_stage():
    """Streaming-append attribution: a worker.append span with a truthy
    `carry_hit` attr charges its window to the `carry_hit` pseudo-stage
    (the O(ΔT) advance); a checkpoint-miss full reprice — same span name,
    no flag — stays execute. Stage seconds still sum exactly to the e2e
    window, and the BENCH straggler digest path is unaffected (the stage
    participates in summarize like any other)."""
    tid = obs.new_trace_id()
    spans = [
        {"ev": "span", "name": "job", "t0": 0.0, "dur_s": 3.0,
         "trace_id": tid, "span_id": "s0", "job": "a1", "worker": "w0"},
        {"ev": "span", "name": "job.queue_wait", "t0": 0.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s1", "job": "a1"},
        {"ev": "span", "name": "worker.append", "t0": 1.5, "dur_s": 0.25,
         "trace_id": tid, "span_id": "s2", "carry_hit": True},
        {"ev": "span", "name": "worker.report", "t0": 2.5, "dur_s": 0.5,
         "trace_id": tid, "span_id": "s3"},
    ]
    tls = timeline.reconstruct(spans)
    stages = timeline.critical_path(tls[tid])
    assert stages["carry_hit"] == pytest.approx(0.25)
    assert stages["execute"] == 0.0
    assert sum(stages.values()) == pytest.approx(3.0)
    summary = timeline.summarize(tls)
    assert summary["stages"]["carry_hit"]["total_s"] == pytest.approx(0.25)

    # A full reprice (no carry_hit flag) is ordinary execute work.
    spans[2] = dict(spans[2], carry_hit=False)
    stages = timeline.critical_path(timeline.reconstruct(spans)[tid])
    assert stages["execute"] == pytest.approx(0.25)
    assert stages["carry_hit"] == 0.0


def test_timeline_push_stage():
    """Live fan-out attribution (serve/): a `job.push` span — the
    dispatcher-side completion->fanned-out window, emitted before the
    e2e span closes — charges its window to the `push` stage; stage
    seconds still sum exactly to the e2e window. The span overlapping
    the worker's report ENVELOPE wins it (priority 2 vs 1): those
    instants are fan-out work, not report wall."""
    tid = obs.new_trace_id()
    spans = [
        {"ev": "span", "name": "job", "t0": 0.0, "dur_s": 3.0,
         "trace_id": tid, "span_id": "s0", "job": "p1", "worker": "w0"},
        {"ev": "span", "name": "job.queue_wait", "t0": 0.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s1", "job": "p1"},
        {"ev": "span", "name": "worker.report", "t0": 2.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s2"},
        {"ev": "span", "name": "job.push", "t0": 2.8, "dur_s": 0.2,
         "trace_id": tid, "span_id": "s3", "job": "p1"},
    ]
    tls = timeline.reconstruct(spans)
    stages = timeline.critical_path(tls[tid])
    assert stages["push"] == pytest.approx(0.2)
    assert stages["report"] == pytest.approx(0.8)
    assert sum(stages.values()) == pytest.approx(3.0)
    summary = timeline.summarize(tls)
    assert summary["stages"]["push"]["total_s"] == pytest.approx(0.2)


def test_timeline_inflight_span_charges_execute_not_transport():
    """Round 14: the pipelined worker's `worker.inflight` span (the
    submit-return -> collect-start window while the batch runs on
    device) charges to execute at envelope priority — without it the
    analyzer's uncovered-gap rule would mis-charge the overlap window to
    transport. Stage seconds still sum exactly to the e2e window."""
    tid = obs.new_trace_id()
    spans = [
        {"ev": "span", "name": "job", "t0": 0.0, "dur_s": 4.0,
         "trace_id": tid, "span_id": "s0", "job": "j1", "worker": "w0"},
        {"ev": "span", "name": "job.queue_wait", "t0": 0.0, "dur_s": 0.5,
         "trace_id": tid, "span_id": "s1", "job": "j1"},
        {"ev": "span", "name": "worker.submit", "t0": 1.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s2"},
        {"ev": "span", "name": "worker.inflight", "t0": 2.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s3"},
        {"ev": "span", "name": "worker.collect", "t0": 3.0, "dur_s": 1.0,
         "trace_id": tid, "span_id": "s4"},
        # The collect lane's split: the device finishing the group is
        # execute, the copy back and the packing are d2h.
        {"ev": "span", "name": "worker.device_wait", "t0": 3.0,
         "dur_s": 0.25, "trace_id": tid, "span_id": "s5", "parent_id": "s4"},
        {"ev": "span", "name": "worker.d2h", "t0": 3.25, "dur_s": 0.25,
         "trace_id": tid, "span_id": "s6", "parent_id": "s4"},
        {"ev": "span", "name": "worker.pack", "t0": 3.5, "dur_s": 0.4,
         "trace_id": tid, "span_id": "s7", "parent_id": "s4"},
        # The compute thread's waits carry no trace: no stage of any job.
        {"ev": "span", "name": "worker.wait_batch", "t0": 0.5,
         "dur_s": 0.5, "span_id": "s8"},
    ]
    tls = timeline.reconstruct(spans)
    assert set(tls) == {tid}
    stages = timeline.critical_path(tls[tid])
    # submit + inflight + the device wait
    assert stages["execute"] == pytest.approx(2.25)
    assert stages["d2h"] == pytest.approx(0.75)   # d2h + pack + envelope
    assert stages["transport"] == pytest.approx(0.5)  # only the real gap
    assert sum(stages.values()) == pytest.approx(4.0)

    # Without the inflight span the same window reads as transport —
    # the mis-charge the overlap-aware mode exists to prevent.
    stages = timeline.critical_path(
        timeline.reconstruct(spans[:3] + spans[4:])[tid])
    assert stages["transport"] == pytest.approx(1.5)


def test_timeline_overlap_factor_pipelined_vs_serial():
    """The overlap-aware mode's `overlap_factor` (round 14): lane
    seconds (submit-half + collect-half) per covered wall second on the
    job's worker — ~1.0 for a serial worker whose lanes tile the busy
    wall, rising toward 2.0 when batch N's device drain overlaps batch
    N+1's host submit. A multi-job batch's fanned-out span lands in the
    lane union once, so co-batching alone never reads as pipelining."""
    tid_a, tid_b = obs.new_trace_id(), obs.new_trace_id()

    def job_spans(tid, name, t0, dur):
        return [
            {"ev": "span", "name": "job", "t0": t0, "dur_s": dur,
             "trace_id": tid, "span_id": f"{name}-e2e", "job": name,
             "worker": "w0"},
            {"ev": "span", "name": "job.queue_wait", "t0": t0,
             "dur_s": 0.5, "trace_id": tid, "span_id": f"{name}-q",
             "job": name}]

    def pipeline_spans():
        return (
            job_spans(tid_a, "A", 0.0, 4.0)
            + job_spans(tid_b, "B", 0.5, 4.5)
            + [
                {"ev": "span", "name": "worker.submit", "t0": 1.0,
                 "dur_s": 1.0, "trace_id": tid_a, "span_id": "a-sub"},
                # Fanned-out decode (a shared-batch span) inside A's
                # submit window: present in BOTH timelines, counted once.
                {"ev": "span", "name": "worker.decode", "t0": 1.0,
                 "dur_s": 0.5, "span_id": "shared-dec", "parent_id": "",
                 "traces": [[tid_a, ""], [tid_b, ""]]},
                {"ev": "span", "name": "worker.inflight", "t0": 2.0,
                 "dur_s": 0.5, "trace_id": tid_a, "span_id": "a-inf"},
                {"ev": "span", "name": "worker.collect", "t0": 2.5,
                 "dur_s": 1.5, "trace_id": tid_a, "span_id": "a-col"},
                # A's collect split: all inside the collect lane already.
                {"ev": "span", "name": "worker.device_wait", "t0": 2.5,
                 "dur_s": 0.5, "trace_id": tid_a, "span_id": "a-dw"},
                {"ev": "span", "name": "worker.d2h", "t0": 3.0,
                 "dur_s": 0.25, "trace_id": tid_a, "span_id": "a-d2h"},
                {"ev": "span", "name": "worker.pack", "t0": 3.25,
                 "dur_s": 0.75, "trace_id": tid_a, "span_id": "a-pack"},
                # B's submit overlaps A's collect drain: the pipeline.
                {"ev": "span", "name": "worker.submit", "t0": 2.0,
                 "dur_s": 2.0, "trace_id": tid_b, "span_id": "b-sub"},
                {"ev": "span", "name": "worker.collect", "t0": 4.0,
                 "dur_s": 1.0, "trace_id": tid_b, "span_id": "b-col"},
            ])

    tls = timeline.reconstruct(pipeline_spans())
    s = timeline.summarize(tls, overlap=True)
    # Lanes on w0: submit [1,4] (3s), collect [2.5,5] (2.5s), covered
    # wall [1,5] (4s) -> fleet factor 5.5/4.
    assert s["overlap"]["overlap_factor"] == pytest.approx(1.375)
    assert s["overlap"]["workers"]["w0"] == pytest.approx(1.375)
    assert s["overlap"]["lane_seconds"]["submit"] == pytest.approx(3.0)
    assert s["overlap"]["lane_seconds"]["collect"] == pytest.approx(2.5)
    by_job = {j["job"]: j for j in s["per_job"]}
    # A's window [0,4]: submit 3s + collect 1.5s over 3s covered wall.
    assert by_job["A"]["overlap_factor"] == pytest.approx(1.5)
    assert by_job["B"]["overlap_factor"] == pytest.approx(1.375)

    # Serial twin: same stage walls, lanes tiling the busy wall -> 1.0
    # everywhere (and overlap=False keeps the key out entirely).
    serial = (
        job_spans(tid_a, "A", 0.0, 3.5)
        + job_spans(tid_b, "B", 2.5, 2.5)
        + [
            {"ev": "span", "name": "worker.submit", "t0": 1.0,
             "dur_s": 1.0, "trace_id": tid_a, "span_id": "a-sub"},
            {"ev": "span", "name": "worker.collect", "t0": 2.0,
             "dur_s": 1.0, "trace_id": tid_a, "span_id": "a-col"},
            {"ev": "span", "name": "worker.submit", "t0": 3.0,
             "dur_s": 1.0, "trace_id": tid_b, "span_id": "b-sub"},
            {"ev": "span", "name": "worker.collect", "t0": 4.0,
             "dur_s": 1.0, "trace_id": tid_b, "span_id": "b-col"},
        ])
    s = timeline.summarize(timeline.reconstruct(serial), overlap=True)
    assert s["overlap"]["overlap_factor"] == pytest.approx(1.0)
    assert all(j["overlap_factor"] == pytest.approx(1.0)
               for j in s["per_job"])
    s_off = timeline.summarize(timeline.reconstruct(serial))
    assert "overlap" not in s_off
    assert all("overlap_factor" not in j for j in s_off["per_job"])

    # The in-memory ring hook (bench's entry point) passes the mode
    # through and keeps the digest-not-rows discipline.
    ring_summary = timeline.summarize_spans(pipeline_spans(), overlap=True)
    assert ring_summary["overlap"]["overlap_factor"] == pytest.approx(1.375)
    assert "per_job" not in ring_summary


def test_event_log_env_opt_in_is_lazy(tmp_path, monkeypatch):
    """DBX_OBS_JSONL is consulted at FIRST USE, not import (dbxlint
    import-time-config): setting it after import but before first use
    enables logging, and an explicit configure() always wins over the
    environment — in-process toggling, no reimport."""
    path = str(tmp_path / "lazy.jsonl")
    monkeypatch.setattr(events, "_env_checked", False)
    monkeypatch.setattr(events, "_fh", None)
    monkeypatch.setattr(events, "_path", None)
    monkeypatch.setenv("DBX_OBS_JSONL", path)
    try:
        assert events.enabled()                    # first use reads the env
        assert events.configured_path() == path
        events.emit("lazy_probe", k=1)
        assert json.loads(open(path).read())["ev"] == "lazy_probe"
        # Explicit configure(None) disables even though the env is set.
        events.configure(None)
        assert not events.enabled()
    finally:
        events.configure(None)


def test_steptimer_gauge():
    reg = obs.Registry()
    g = reg.gauge("dbx_rate")
    t = obs.StepTimer(g)
    t.add(100)
    assert t.rate > 0
    assert g.value > 0   # published at add() time (rate decays after)


# ---------------------------------------------------------------------------
# utils.trace deprecation shim
# ---------------------------------------------------------------------------

def test_utils_trace_shim_warns_and_reexports():
    import importlib
    import warnings

    import distributed_backtesting_exploration_tpu.utils.trace as shim

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        importlib.reload(shim)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    from distributed_backtesting_exploration_tpu.obs import trace as obs_trace

    assert shim.timed is obs_trace.timed
    assert shim.StepTimer is obs_trace.StepTimer
    assert shim.device_profile is obs_trace.device_profile


# ---------------------------------------------------------------------------
# Fused-kernel substrate observability (worker backend)
# ---------------------------------------------------------------------------

def test_backend_publishes_substrate_info_and_route_counters(monkeypatch):
    """A fleet operator must be able to read which epilogue/table/lane
    substrate a worker serves from GetStats obs_json / /stats.json alone:
    the backend publishes an info gauge at construction and counts every
    fused group into dbx_fused_substrate_total."""
    import numpy as np

    from distributed_backtesting_exploration_tpu.rpc import (
        backtesting_pb2 as pb, compute, wire)
    from distributed_backtesting_exploration_tpu.rpc.dispatcher import (
        synthetic_jobs)

    monkeypatch.delenv("DBX_EPILOGUE", raising=False)
    monkeypatch.delenv("DBX_SMA_TABLE", raising=False)
    monkeypatch.delenv("DBX_LANES_CAP", raising=False)
    backend = compute.JaxSweepBackend(use_fused=True, use_mesh=False)
    summ = obs.get_registry().summaries(prefix="dbx_fused_substrate_info")
    info = [k for k in summ if "epilogue=scan" in k]
    assert info, f"substrate info gauge missing: {summ}"
    assert any("table_sma=inline" in k and "lanes_cap=0" in k for k in info)

    (rec,) = synthetic_jobs(1, 64, "sma_crossover",
                            {"fast": np.asarray([3.0], np.float32),
                             "slow": np.asarray([10.0], np.float32)},
                            seed=5)
    spec = pb.JobSpec(id=rec.id, strategy=rec.strategy, ohlcv=rec.ohlcv,
                      grid=wire.grid_to_proto(rec.grid), cost=rec.cost,
                      periods_per_year=252)
    (done,) = backend.process([spec])
    assert done.metrics   # the group really ran fused
    summ = obs.get_registry().summaries(prefix="dbx_fused_substrate_total")
    key = [k for k in summ
           if "kernel=sma_crossover" in k and "epilogue=scan" in k
           and "table=inline" in k]
    assert key and summ[key[0]] >= 1
