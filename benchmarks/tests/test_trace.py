"""The trace reduction, on interval arithmetic and on a small recorded
trace of a v5e (``fixtures/tpu_window.xplane.pb``, made by
``record_trace_fixture.py``: three runs of one small program inside
``bench.window``, each followed by a 20 ms host sleep annotated
``bench.enqueue``)."""

import os

import pytest

from harness import peaks
from harness import trace as trace_mod

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tpu_window.xplane.pb")


def test_union_merges_overlaps_and_drops_empty():
    assert trace_mod.union([(5, 7), (1, 3), (2, 4), (8, 8)]) == [(1, 4),
                                                                (5, 7)]
    assert trace_mod.measure([(1, 4), (5, 7)]) == 5


def test_subtract_leaves_the_uncovered_parts():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (5, 22), (29, 40)]
    assert trace_mod.subtract(a, b) == [(0, 2), (3, 5), (22, 29)]
    assert trace_mod.subtract(a, []) == a
    assert trace_mod.subtract([], b) == []


@pytest.fixture(scope="module")
def red():
    return trace_mod.reduce(FIXTURE)


def test_fixture_window_busy_and_idle(red):
    # Three 20 ms sleeps lie in the window, so it is over 60 ms long and
    # the device is idle for most of it.
    assert red.devices == ["/device:TPU:0"]
    assert 0.06 < red.window_s < 1.0
    assert 0.0 < red.busy_s < red.window_s
    assert red.idle_share > 0.5


def test_fixture_per_module_and_op_time(red):
    assert red.module_s and red.op_s
    total_ops = sum(red.op_s.values())
    # Device ops of one program do not overlap here: their sum is the
    # busy time.
    assert total_ops == pytest.approx(red.busy_s, rel=1e-6)
    assert red.module_seconds(r"jit_") > 0.0
    assert red.module_seconds(r"no such module") == 0.0
    assert red.top_ops(2)[0][1] >= red.top_ops(2)[-1][1]


def test_fixture_has_no_collectives(red):
    assert red.collective_exposed_s == 0.0


def test_fixture_gaps_are_labelled_by_the_host(red):
    gaps = red.idle_gaps
    assert len(gaps) >= 3
    assert gaps[0][1] >= gaps[-1][1]
    # The three longest gaps are the annotated sleeps.
    assert [g[0] for g in gaps[:3]] == ["bench.enqueue"] * 3
    assert all(0.019 < g[1] < 0.2 for g in gaps[:3])


def test_roofline_share_uses_published_peaks():
    kind = "TPU v5 lite"
    p = peaks.peaks(kind)
    assert peaks.roofline_share(p["ops_per_s"], 0.0, 1.0, kind) == \
        pytest.approx(100.0)
    assert peaks.roofline_share(0.0, 1.0, 1.0, kind) is None
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
