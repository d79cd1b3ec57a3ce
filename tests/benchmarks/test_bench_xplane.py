"""The reduction from a trace to busy seconds, gaps and top operations."""

import os

import pytest

from bench_testlib import ROOT

from benchmarks import xplane

RECORDED = os.path.join(ROOT, "tests", "benchmarks", "data",
                        "small_trace.xplane.pb")


def synthetic():
    dev = [("fusion.1", 1.0, 2.0), ("fusion.2", 2.0, 2.5),   # adjacent
           ("copy", 4.0, 4.5), ("fusion.1", 9.5, 11.0)]      # runs past
    host = [(xplane.WINDOW_SPAN, 0.0, 10.0), ("dispatch", 0.0, 0.9),
            ("fetch", 2.6, 3.9), ("build trees", 4.6, 9.4)]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_merge_and_clip():
    assert xplane.merge([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert xplane.clip([(0, 2), (5, 9)], 1, 6) == [(1, 2), (5, 6)]


def test_reduce_synthetic_trace():
    r = xplane.reduce(synthetic())
    # the window span, widened to the operation that runs past it
    assert r["window_s"] == pytest.approx(11.0)
    # union: [1, 2.5] + [4, 4.5] + [9.5, 11]
    assert r["busy_s"] == pytest.approx(1.5 + 0.5 + 1.5)
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(1.0 + 1.5)
    gaps = r["idle_gaps"]
    assert gaps[0][0] == "build trees" and gaps[0][1] == pytest.approx(5.0)
    assert gaps[1] == ["fetch", pytest.approx(1.5)]
    assert gaps[2] == ["dispatch", pytest.approx(1.0)]
    assert len(r["device_ops"]) <= 10 and len(gaps) <= 10


def test_nested_operations_count_once():
    body = [("%while.1 = (s32[]) while(%t), body=%b", 0.0, 10.0),
            ("%fusion.2 = f32[3,1792]{1,0} fusion(%a), kind=kLoop", 1.0, 4.0),
            ("%fusion.2 = f32[3,1792]{1,0} fusion(%a), kind=kLoop", 5.0, 8.0)]
    t = {"devices": {"/device:TPU:0": body}, "host": []}
    r = xplane.reduce(t)
    assert r["busy_s"] == pytest.approx(10.0)
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["%fusion.2 fusion f32[3,1792]"] == pytest.approx(6.0)
    assert ops["%while.1 while tuple"] == pytest.approx(4.0)


def test_no_device_work_reads_nothing():
    t = synthetic()
    host = t["host"]
    assert xplane.reduce({"devices": {"/device:TPU:0": []},
                          "host": host}) is None
    assert xplane.reduce({"devices": {}, "host": host}) is None


def test_window_falls_back_to_the_device_operations():
    t = synthetic()
    t["host"] = []
    assert xplane.window_of(t) == (1.0, 11.0)
    t["devices"] = {"/device:TPU:0": []}
    assert xplane.window_of(t) is None


def test_recorded_chip_trace():
    """A trace recorded on the v5e (two contraction passes of 65,536 x 28
    rows, 10 ms of host sleep between them, inside the window span)."""
    trace = xplane.load(RECORDED)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    r = xplane.reduce(trace)
    assert 0 < r["busy_s"] < r["window_s"]
    # the sleep is idle time and the window holds it
    assert r["window_s"] - r["busy_s"] > 0.010
    assert r["device_ops"] and r["idle_gaps"]
    # both passes are in, though the first starts before the span
    assert 0.0006 < r["busy_s"] < 0.0008
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(r["busy_s"],
                                                               rel=0.05)
    assert r["device_ops"][0][0].startswith("%fusion.15 fusion f32[3,1792]")


def test_busy_and_gaps_in_columns():
    ops = xplane.Ops.of([("c", 5.0, 6.0), ("a", 1.0, 2.0), ("b", 2.0, 3.0),
                         ("d", 5.2, 5.4)])          # d runs inside c
    busy, gaps = xplane.busy_and_gaps(ops, 0.0, 8.0)
    assert busy == pytest.approx(2.0 + 1.0)
    assert gaps == [(0.0, 1.0), (3.0, 5.0), (6.0, 8.0)]
    assert xplane.self_seconds(ops) == pytest.approx(
        {"a": 1.0, "b": 1.0, "c": 0.8, "d": 0.2})
