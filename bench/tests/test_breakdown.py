"""The breakdown by program span and device scope (breakdown.py), and the
per-layer metric that reads the program's counters (metrics/h2d_passes.py):
on hand-made events, and on the TPU trace committed beside this file."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import breakdown as bd  # noqa: E402
import trace_reduce as tr  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "tpu_v5e_small.xplane.pb"


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_tf_op_reader_on_recorded_trace():
    chips = bd.read_device_ops(RECORDED)
    assert list(chips) == ["/device:TPU:0"]
    tf_ops = {tf_op for _, tf_op, _, _ in chips["/device:TPU:0"]}
    assert "jit(<lambda>)/jit(qr)/geqrf:" in tf_ops
    assert "jit(<lambda>)/jit(qr)/householder_product:" in tf_ops


def test_device_ops_agree_with_profile_data():
    """The wire walk finds the same ops at the same times as ProfileData."""
    devices, _ = tr.read_xplane(RECORDED)
    walked = bd.read_device_ops(RECORDED)
    ours = sorted((n, s, e) for n, _, s, e in walked["/device:TPU:0"])
    theirs = sorted(devices["/device:TPU:0"])
    assert [x[0] for x in ours] == [x[0] for x in theirs]
    for (_, s1, e1), (_, s2, e2) in zip(ours, theirs):
        assert s1 == pytest.approx(s2, abs=2e-9) and e1 == pytest.approx(e2, abs=2e-9)


def test_recorded_trace_without_program_spans_names_as_before():
    chips = bd.read_device_ops(RECORDED)
    spans = bd.read_host_spans(RECORDED)
    out = bd.reduce(chips, spans)
    base = tr.reduce_events({c: [(n, s, e) for n, _, s, e in ops]
                             for c, ops in chips.items()}, spans)
    assert out["device_ops"] == base["device_ops"]
    assert out["idle_gaps"] == base["idle_gaps"]
    assert out["scope_busy_s"] == {} and out["span_s"] == {}
    assert out["staging_share"] is None and out["small_svd_share"] is None


def test_scopes_of_a_name_stack():
    stack = "jit(_randomized_svd_dense)/rsvd.small_svd/while/body/rsvd.orth/dot_general:"
    assert bd.scopes_of(stack) == ["rsvd.small_svd", "rsvd.orth"]
    assert bd.scopes_of(None) == bd.scopes_of("jit(f)/dot_general:") == []
    assert bd.op_label("fusion.3", bd.scopes_of(stack)) == "rsvd.orth/fusion.3"
    assert bd.op_label("fusion.3", []) == "fusion.3"


def test_unscoped_op_takes_the_scopes_its_nested_ops_share():
    """A TPU trace gives a `while` no `tf_op`; its body's ops have one."""
    ops = [("while.13", None, 1.0, 3.0),
           ("fusion.1", "jit(f)/rsvd.small_svd/while/body/rsvd.orth/x:", 1.1, 1.5),
           ("fusion.2", "jit(f)/rsvd.small_svd/while/body/y:", 2.0, 2.5),
           ("copy", None, 1.6, 1.7),
           ("while.2", None, 5.0, 6.0),
           ("fusion.3", "jit(f)/rsvd.sketch/z:", 5.5, 7.0),   # not nested
           ("while.3", None, 8.0, 9.0),
           ("fusion.4", "jit(f)/rsvd.sketch/z:", 8.1, 8.2),
           ("fusion.5", "jit(f)/rsvd.power/z:", 8.3, 8.4)]
    assert bd.nested_scopes(ops) == [
        ["rsvd.small_svd"], ["rsvd.small_svd", "rsvd.orth"], ["rsvd.small_svd"],
        [], [], ["rsvd.sketch"], [], ["rsvd.sketch"], ["rsvd.power"]]


SCOPED = "jit(_randomized_svd_dense)/rsvd.small_svd/while"
CHIPS = {"/device:TPU:0": [
    ("while.13", None, 1.0, 3.0),                 # the loop ...
    ("fusion.284", SCOPED + "/body", 1.5, 2.0),   # ... and its body, nested
    ("fusion.7", "jit(f)/rsvd.sketch/pallas_call:", 3.0, 3.5),
    ("copy", None, 9.0, 9.5),
]}
SPANS = [
    ("window", 0.0, 10.0),
    ("call", 0.0, 8.0), ("wait", 8.0, 10.0),
    ("linalg.decompose", 0.0, 8.0),
    ("linalg.plan", 0.1, 0.2),
    ("linalg.execute", 0.5, 8.0),
    ("engine.project", 3.5, 8.0),
    ("pipeline.panel", 3.6, 7.9),
    ("pipeline.put", 3.7, 7.8),
]


def test_op_and_gap_names_gain_scope_and_span():
    out = bd.reduce(CHIPS, SPANS)
    ops = dict(out["device_ops"])
    assert ops["rsvd.small_svd/while.13"] == pytest.approx(2.0)
    assert ops["rsvd.small_svd/fusion.284"] == pytest.approx(0.5)
    assert ops["rsvd.sketch/fusion.7"] == pytest.approx(0.5)
    assert ops["copy"] == pytest.approx(0.5)
    # idle on the chip: [0, 1], [3.5, 9], [9.5, 10]
    assert out["idle_gaps"] == [["call/pipeline.put", pytest.approx(5.5)],
                                ["call/linalg.execute", pytest.approx(1.0)],
                                ["wait", pytest.approx(0.5)]]


def test_scope_busy_counts_nested_ops_once():
    out = bd.reduce(CHIPS, SPANS)
    assert out["scope_busy_s"] == {"rsvd.small_svd": pytest.approx(2.0),
                                   "rsvd.sketch": pytest.approx(0.5)}
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["small_svd_share"] == pytest.approx(100.0 * 2.0 / 3.0)


def test_span_shares_and_front_end():
    out = bd.reduce(CHIPS, SPANS)
    assert out["solves"] == 1
    assert out["staging_share"] == pytest.approx(100.0 * 4.3 / 10.0)
    # linalg.decompose 8.0 s, of which linalg.execute covers 7.5
    assert out["frontend_ms"] == pytest.approx(500.0)
    idle = 10.0 - 3.0
    assert out["idle_share_under_span"]["pipeline.put"] == pytest.approx(4.1 / idle)
    assert out["idle_share_under_span"]["linalg.decompose"] == pytest.approx(5.5 / idle)


def test_innermost_span_needs_half_the_gap():
    spans = [("outer", 0.0, 10.0), ("inner", 2.0, 3.0)]
    assert bd.innermost_span((2.0, 3.5), spans) == "inner"
    assert bd.innermost_span((2.5, 5.0), spans) == "outer"
    assert bd.innermost_span((20.0, 21.0), spans) is None


def test_intersect_of_merged_lists():
    assert bd.intersect_s([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2.0)
    assert bd.intersect_s([], [(1, 4)]) == 0.0


def test_h2d_passes_reads_the_program_counters(monkeypatch):
    from repro.linalg import trace

    read = _metric("h2d_passes")
    ctx = {"pass_bytes": 16_000_000}
    monkeypatch.setattr(trace, "counters", lambda: {
        "linalg.solves": 10, "pipeline.h2d_bytes": 6 * 16_000_000 * 10})
    assert read(ctx) == pytest.approx(6.0)
    monkeypatch.setattr(trace, "counters", lambda: {"linalg.solves": 3})
    assert read(ctx) is None


def test_h2d_passes_reads_nothing_without_the_counters(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.linalg.trace", None)
    assert _metric("h2d_passes")({"pass_bytes": 1}) is None
