"""The reduction from a profiler trace to busy time, top operations and
idle gaps: on hand-made event lists, and on a small trace recorded on a
TPU v5e chip (``data/trace_fixture.xplane.pb``: three dispatches of a
2048 x 2048 bf16 matmul chain, each followed by a 4 ms host sleep inside
a ``bench.host_gap`` span)."""
import os

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "trace_fixture.xplane.pb")


def test_union_gaps_and_clip():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.clip([(0, 3), (5, 9)], 1, 6) == [(1, 3), (5, 6)]
    assert trace.gaps([(1, 3), (5, 6)], 0, 8) == [(0, 1), (3, 5), (6, 8)]


def test_reduce_names_gaps_by_the_open_host_span():
    data = {
        "devices": {"/device:TPU:0": [("fusion.1", 0.0, 100.0),
                                      ("fusion.2", 50.0, 100.0),
                                      ("fusion.1", 150.0, 300.0)]},
        "spans": [("bench.trace_window", 0.0, 400.0),
                  ("bench.loss_fetch", 90.0, 160.0),
                  ("bench.feed", 300.0, 400.0),
                  ("bench.dispatch", 0.0, 400.0)],
    }
    r = trace.reduce(data)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    assert r["idle_gaps"] == [["bench.feed", pytest.approx(100e-9)],
                              ["bench.loss_fetch", pytest.approx(50e-9)]]


def test_reduce_finds_nothing_without_device_ops():
    assert trace.reduce({"devices": {}, "spans": []}) is None


def test_reduce_averages_busy_over_devices():
    data = {"devices": {"/device:TPU:0": [("a", 0.0, 10.0)],
                        "/device:TPU:1": [("a", 0.0, 30.0)]},
            "spans": [("bench.trace_window", 0.0, 40.0)]}
    r = trace.reduce(data)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["device_ops"] == [["a", pytest.approx(20e-9)]]


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no fixture")
def test_recorded_chip_trace():
    r = trace.reduce(trace.read(FIXTURE))
    assert 0 < r["busy_s"] < r["window_s"]
    # three 4 ms host sleeps: the longest gaps sit in them (the gaps of a
    # few ns between back-to-back ops are left aside)
    sleeps = [s for n, s in r["idle_gaps"]
              if n == "bench.host_gap" and s > 1e-3]
    assert len(sleeps) >= 2
    assert all(0.003 < s < 0.02 for s in sleeps)
    assert r["device_ops"] and r["device_ops"][0][1] > 0
