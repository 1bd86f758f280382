"""The required work of the configuration (its kind's), the least time on
the cell's chips, and the peaks table."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import work  # noqa: E402

dense_spectrum = run._module(BENCH / "kinds", "dense_spectrum", "kind")


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_paper_work():
    w = dense_spectrum.required_work(config("paper_fast_2000x2000"))
    assert w == {"flops": 6 * 2 * 2000 * 2000 * 210, "bytes": 3 * 2000 * 2000 * 4}
    assert w["flops"] == 10_080_000_000           # 10.08 GFLOP
    assert w["bytes"] == 48_000_000               # 48 MB
    t, bound = work.least_time_s(w, work.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(58.61e-6, rel=1e-3)
    assert w["flops"] / 197e12 == pytest.approx(51.17e-6, rel=1e-3)
    # four chips share the work: a quarter of one chip's least time
    t4, bound4 = work.least_time_s(w, work.peaks_for("TPU v5 lite"), chips=4)
    assert (t4, bound4) == (pytest.approx(t / 4, rel=1e-12), "memory")


def test_work_ignores_how_the_solve_runs():
    cfg = config("paper_fast_2000x2000")
    assert (dense_spectrum.required_work(dict(cfg, fused_power=False))
            == dense_spectrum.required_work(cfg))
    assert (dense_spectrum.required_work(dict(cfg, placement="rows"))
            == dense_spectrum.required_work(cfg))


def test_tolerance_work_prices_the_smallest_rank_that_meets_it():
    """Under a tolerance the rank is the smallest whose optimal error meets
    eps, from the exact spectrum: 1/i^2 at eps 1e-3 needs 69 of 2000."""
    cfg = dict(config("paper_fast_2000x2000"), spec={"kind": "tolerance", "eps": 1e-3})
    sigma = dense_spectrum.spectrum("inverse_square", 2000)
    k = dense_spectrum.min_rank(sigma, 1e-3)
    assert dense_spectrum.optimal_ratio(sigma, k) <= 1e-3 < dense_spectrum.optimal_ratio(
        sigma, k - 1)
    w = dense_spectrum.required_work(cfg)
    assert w["flops"] == 6 * 2 * 2000 * 2000 * (k + 10)
    assert w["bytes"] == 3 * 2000 * 2000 * 4


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
