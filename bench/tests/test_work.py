"""The required work of the configuration, and the peaks table."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_paper_work():
    w = work.required_work(config("paper_fast_2000x2000"))
    assert w == {"flops": 6 * 2 * 2000 * 2000 * 210, "bytes": 3 * 2000 * 2000 * 4}
    assert w["flops"] == 10_080_000_000           # 10.08 GFLOP
    assert w["bytes"] == 48_000_000               # 48 MB
    t, bound = work.least_time_s(w, work.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(58.61e-6, rel=1e-3)
    assert w["flops"] / 197e12 == pytest.approx(51.17e-6, rel=1e-3)


def test_work_ignores_how_the_solve_runs():
    cfg = config("paper_fast_2000x2000")
    assert work.required_work(dict(cfg, fused_power=False)) == work.required_work(cfg)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
