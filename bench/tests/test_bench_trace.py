"""The trace reduction: busy union, idle share, top ops, named gaps."""

import os

import pytest

import trace_reduce as tr
from trace_reduce import Interval

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_counts_overlap_once():
    assert tr.union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert tr.union_length([]) == 0


def test_idle_gaps_are_the_uncovered_stretches():
    assert tr.idle_gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [
        (0, 2), (6, 8), (9, 10)]
    assert tr.idle_gaps([(0, 10)], 2, 8) == []


def test_reduce_synthetic_trace():
    dev = {"/device:TPU:0": [Interval("fusion.1", 10, 40),
                             Interval("fusion.2", 35, 60),
                             Interval("copy", 80, 90),
                             Interval("outside", 200, 300)]}
    host = [Interval("bench.window", 0, 100),
            Interval("bench.step", 0, 65), Interval("bench.wait", 65, 100)]
    r = tr.reduce_events(dev, host)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(60e-9)
    assert [n for n, _ in r.device_ops] == ["fusion.1", "fusion.2", "copy"]
    assert r.idle_gaps[0] == ["bench.wait", pytest.approx(20e-9)]
    assert sorted(n for n, _ in r.idle_gaps) == [
        "bench.step", "bench.wait", "bench.wait"]


def test_two_devices_average():
    dev = {"/device:TPU:0": [Interval("a", 0, 50)],
           "/device:TPU:1": [Interval("a", 0, 100)]}
    r = tr.reduce_events(dev, [Interval("bench.window", 0, 100)])
    assert r.busy_s == pytest.approx(75e-9) and r.n_devices == 2


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": []}, [])


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: 4 serve-like steps of one 1024^2
    bf16 product, each followed by a ~2.5 ms host wait."""
    dev, host = tr.load(os.path.join(DATA, "v5e_small.xplane.pb"))
    assert list(dev) == ["/device:TPU:0"]
    names = {e.name for e in dev["/device:TPU:0"]}
    assert "fusion" in names and "copy-start" in names
    r = tr.reduce_events(dev, host)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(13.791038e-3)
    assert 0 < r.busy_s < 0.01 * r.window_s
    assert r.device_ops[0][0] == "fusion"
    assert r.device_ops[0][1] == pytest.approx(4 * 12.62e-6, rel=0.01)
    assert {n for n, _ in r.idle_gaps} <= {"bench.step", "bench.wait"}
    assert "bench.wait" in {n for n, _ in r.idle_gaps}


def test_clock_offset_puts_each_program_after_its_launch():
    assert tr.clock_offset([10.0, 50.0], [12.0, 51.0]) == 2.0
    assert tr.clock_offset([10.0, 50.0], [9.0, 40.0]) == 0.0
    assert tr.clock_offset([10.0], [12.0, 51.0]) == 0.0
