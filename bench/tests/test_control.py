"""The control: the plain reference in the program's place, run through the
harness's own comparison under each cell's committed limits, at the cell's
own configuration on the CPU (an open loop's over a short window).  With
its products at one bf16 pass it must come out not correct; at `highest`
it must pass."""
from __future__ import annotations

import json
import shutil

import pytest

from test_rehearsal import (BENCH, ROOT, TOLERANCE, TOLERANCE_LIMITS, make_checkout,
                             run_cell)

WORKLOADS = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
CELLS = [w["name"] for w in WORKLOADS]
#: an open loop's control answers each request in the sender, at the cell's
#: own rate: its window is short
SECONDS = {w["name"]: 0.5 if json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                                        .read_text())["loop"] == "open" else 3.0
           for w in WORKLOADS}


def checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


@pytest.mark.parametrize("precision", ["bf16", "highest"])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_reference_passes(tmp_path, cell, precision):
    root = checkout(tmp_path)
    patch = f"import control; control.plant({cell!r}, {precision!r}, run.ROOT)"
    result = run_cell(root, cell, patch=patch, seconds=SECONDS[cell])
    limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
    assert {name: c["limit"] for name, c in result["check"].items()} == limits
    assert result["failed"] == 0
    assert result["correct"] == (precision == "highest"), result["check"]


@pytest.mark.parametrize("precision", ["bf16", "highest"])
def test_control_under_a_tolerance_spec(tmp_path, precision):
    """The control answers a tolerance spec too: it factors at full rank
    and keeps the fewest leading components whose residual meets eps."""
    root = make_checkout(tmp_path, "device", limits=TOLERANCE_LIMITS,
                         config={"spec": TOLERANCE})
    patch = ("import control, pathlib; "
             f"control.plant('tiny.device', {precision!r}, pathlib.Path({str(root)!r}))")
    result = run_cell(root, "tiny.device", patch=patch, seconds=3.0)
    assert result["failed"] == 0
    assert result["correct"] == (precision == "highest"), result["check"]
