"""The trace reduction: interval arithmetic on made-up intervals, and
the whole reduction on a small trace recorded on a v5e (a toy program
run three times between host sleeps, inside ``bench:*`` spans)."""
import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total(tr.union([(0, 10), (2, 3)])) == 10


def test_gaps_cover_the_window_outside_busy():
    busy = [(2, 4), (6, 9)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_intersect_keeps_the_overlaps():
    assert tr.intersect([(0, 4), (6, 10)], [(2, 7), (9, 12)]) == \
        [(2, 4), (6, 7), (9, 10)]
    assert tr.intersect([(0, 2)], [(2, 4)]) == []


def test_clip_events_cuts_to_the_window():
    ev = [(0, 5, "a"), (8, 12, "b"), (20, 30, "c")]
    assert tr.clip_events(ev, 2, 10) == [(2, 5, "a"), (8, 10, "b")]


def test_self_times_subtract_nested_ops():
    # A while loop spanning two fusions, then a lone copy.
    ev = [(0, 100, "while.1"), (10, 30, "fusion.1"), (40, 90, "fusion.2"),
          (100, 110, "copy.3")]
    assert tr.self_times(ev) == {"while.1": 30, "fusion.1": 20,
                                 "fusion.2": 50, "copy.3": 10}


def test_op_name_keeps_the_instruction():
    assert tr.op_name("%fusion.12 = s32[8]{0} fusion(%p)") == "fusion.12"
    assert tr.op_name("all-reduce.3") == "all-reduce.3"


@pytest.fixture(scope="module")
def red():
    pytest.importorskip("jax")
    return tr.Reduction(FIXTURE)


def test_fixture_window_and_busy(red):
    assert list(red.devices) == ["/device:TPU:0"]
    # Three runs of the toy program between 30 ms of host sleeps.
    busy = red.busy("/device:TPU:0")
    assert len(busy) >= 3
    assert 0 < red.busy_s() * 1e9 < red.window_ns
    idle = red.idle_share()
    assert 0.0 < idle < 1.0
    assert red.window_ns >= 3 * 30e6


def test_fixture_module_times(red):
    ns, n = red.module_ns(r"^jit_")
    assert n >= 3
    assert ns == pytest.approx(red.busy_s() * 1e9, rel=0.05)
    assert red.module_ns(r"^no_such_program")[1] == 0


def test_fixture_idle_gaps_are_named_by_host_spans(red):
    gaps = red.idle_gaps(10)
    names = [g[0] for g in gaps]
    assert any(n.startswith("bench:make_unit") for n in names)
    assert any(n.startswith("bench:record") for n in names)
    sleeps = [s for n, s in gaps if n.startswith("bench:make_unit")]
    assert max(sleeps) >= 0.015
    assert all(s > 0 for _, s in gaps)


def test_fixture_top_ops_are_device_ops(red):
    top = red.top_ops(10)
    assert top and all(s >= 0 for _, s in top)
    assert sum(s for _, s in top) <= red.busy_s() * 1.05


def test_fixture_idle_within_host_spans(red):
    sweeps = red.spans("bench:sweep")
    assert len(sweeps) == 3
    dev = "/device:TPU:0"
    busy = tr.total(tr.intersect(red.busy(dev), sweeps))
    assert red.idle_share(within="bench:sweep") == pytest.approx(
        1 - busy / tr.total(sweeps))
    # The host sleeps between the programs lie outside the sweep spans.
    assert red.idle_share(within="bench:sweep") < red.idle_share()
    assert red.idle_share(within="no_such_span") is None
