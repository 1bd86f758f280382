"""The work a rank-k randomized SVD requires, and the least time it can take.

This is the yardstick of `solve_roofline`.  It is computed from the
configuration's sizes alone (m, n, s = k + oversample, q power iterations),
never from the plan the program picks, so that a change which fuses,
unfuses or reorders the solve leaves the yardstick where it was:

- FLOPs: Algorithm 1 multiplies A by an n x s or m x s block 2q + 2 times
  (the sketch, two products per power iteration, the projection), each
  2 m n s operations, fused or not.
- Bytes: q + 1 reads of A, the fewest that any known formulation needs
  (each power iteration's two products share one read of A, and the sketch
  and the projection ride the first and the last).

The least time is the larger of FLOPs at the chip's bf16 peak and bytes at
its HBM bandwidth.  The solves run f32 at `highest` precision, which takes
several bf16 passes per product, so dividing by the bf16 peak keeps the
share under 100%.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def required_work(cfg: dict) -> dict:
    """FLOPs and bytes of one solve."""
    m, n = cfg["m"], cfg["n"]
    s = cfg["rank"] + cfg["oversample"]
    q = cfg["power_iters"]
    itemsize = {"float32": 4}[cfg["dtype"]]
    return {
        "flops": (2 * q + 2) * 2 * m * n * s,
        "bytes": (q + 1) * m * n * itemsize,
    }


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown device
    is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r} "
                       f"in {Path(path).name} (known: {sorted(table)})")
    return table[device_kind]


def least_time_s(work: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, "compute" | "memory"): the least time of `work` at `peaks`
    and which of the two bounds it."""
    t_compute = work["flops"] / peaks["bf16_flops_per_s"]
    t_memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
