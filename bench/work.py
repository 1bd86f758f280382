"""The least time a solve's required work can take on the cell's chips.

This is the yardstick of `solve_roofline`.  The required work, FLOPs and
bytes, is the configuration's kind's (`required_work` in
bench/kinds/<kind>.py), computed from the configuration's sizes alone and
never from the plan the program picks.  The least time is the larger of
FLOPs at the chips' bf16 peak and bytes at their HBM bandwidth, the work
spread evenly over the cell's chips.  The solves run f32 at `highest`
precision, which takes several bf16 passes per product, so dividing by the
bf16 peak keeps the share under 100%.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown device
    is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"in {Path(path).name} (known: {sorted(table)})")
    return table[device_kind]


def least_time_s(work: dict, peaks: dict, chips: int = 1) -> tuple[float, str]:
    """(seconds, "compute" | "memory"): the least time of `work` on `chips`
    chips at one chip's `peaks` each, and which of the two bounds it."""
    t_compute = work["flops"] / (chips * peaks["bf16_flops_per_s"])
    t_memory = work["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
