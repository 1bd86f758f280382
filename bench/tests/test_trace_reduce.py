"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps: on hand-made events, and on a small trace that
was recorded on one TPU v5e and is committed beside this file."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "tpu_v5e_small.xplane.pb"


def test_merge_and_gaps():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([], 0, 1) == [(0, 1)]


def test_reduce_events_by_hand():
    devices = {
        "/device:TPU:0": [("dot", 1.0, 2.0), ("dot", 1.5, 2.5), ("qr", 4.0, 5.0),
                          ("outside", 20.0, 21.0)],
        "/device:TPU:1": [("dot", 1.0, 3.5)],
    }
    spans = [("window", 0.0, 10.0), ("call", 0.0, 1.0), ("wait", 1.0, 5.0),
             ("next_source", 5.0, 5.2), ("call", 5.2, 9.0), ("unrelated", 0, 10)]
    out = tr.reduce_events(devices, spans)
    assert out["window_s"] == 10.0
    assert out["busy_s_per_chip"] == [2.5, 2.5]
    assert out["busy_s"] == 2.5
    assert out["idle_share"] == pytest.approx(0.75)
    assert out["device_ops"] == [["dot", 2.25], ["qr", 0.5]]
    # idle on every chip: [0,1], [3.5,4], [5,10]
    assert out["idle_gaps"] == [["call", 5.0], ["call", 1.0], ["wait", 0.5]]


def test_reduce_events_without_window_or_chip():
    assert tr.reduce_events({"/device:TPU:0": [("a", 0, 1)]}, []) is None
    assert tr.reduce_events({}, [("window", 0, 1)]) is None


def test_recorded_tpu_trace():
    """Five calls of two small jitted programs, traced on one TPU v5e with
    the benchmark's spans."""
    devices, spans = tr.read_xplane(RECORDED)
    assert list(devices) == ["/device:TPU:0"]
    out = tr.reduce_events(devices, spans)
    assert out["window_s"] == pytest.approx(0.021654619)
    assert out["busy_s"] == pytest.approx(0.003255337)
    assert out["idle_share"] == pytest.approx(0.8496700865528958)
    assert out["device_ops"][0][0] == "custom-call.24"
    assert out["idle_gaps"][0] == ["next_source", pytest.approx(0.003700695)]
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share"] < 1
    assert out["device_ops"] and out["idle_gaps"]
    assert {name for name, _ in out["idle_gaps"]} <= {"call", "wait", "next_source",
                                                      "host"}
    seconds = [t for _, t in out["device_ops"]]
    assert seconds == sorted(seconds, reverse=True) and len(seconds) == tr.TOP
