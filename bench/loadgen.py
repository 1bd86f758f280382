"""The one generator every traffic mix goes through.

A traffic mix is a data file under traffic/: where the pool lives (device
or host), how many matrices it holds, how many warm-up calls set-up makes
and how many of the window's solves the check samples.  Closed loop only:
one caller who sends the next call when the last one's factors are ready.
Every call takes a new sketch seed.
"""
from __future__ import annotations

import random

LOOPS = ("closed",)
RESIDENCIES = ("device", "host")


def validate(traffic: dict) -> dict:
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"traffic {traffic.get('name')}: loop must be one of {LOOPS}")
    if traffic.get("residency") not in RESIDENCIES:
        raise ValueError(f"traffic {traffic.get('name')}: residency must be one of "
                         f"{RESIDENCIES}")
    for key in ("pool", "warmup_calls", "check_sample"):
        if not isinstance(traffic.get(key), int) or traffic[key] < 1:
            raise ValueError(f"traffic {traffic.get('name')}: {key} must be a "
                             "positive integer")
    return traffic


def call(traffic: dict, run_seed: int, i: int) -> tuple[int, int]:
    """(pool member, sketch seed) of call i; set-up's warm-up calls are
    calls 0 .. warmup_calls - 1 and the window goes on from there.  The
    seed stays below 2^31: the streamed engine's fused sketch takes its
    seed as a signed 32-bit integer and raises OverflowError above."""
    return i % traffic["pool"], (run_seed + i) % 2 ** 31


class Reservoir:
    """A uniform sample of `size` of the window's solves, drawn from the run
    seed (reservoir sampling: the window's length is not known ahead)."""

    def __init__(self, size: int, run_seed: int):
        self.size = size
        self.items: list = []
        self._seen = 0
        self._rng = random.Random(run_seed)

    def offer(self, item) -> None:
        self._seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = self._rng.randrange(self._seen)
        if j < self.size:
            self.items[j] = item
