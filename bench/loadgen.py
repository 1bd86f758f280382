"""The one generator every traffic mix goes through.

A traffic mix is a data file under traffic/: the loop, the entry the calls
go through, where the pool lives (device or host), how many matrices it
holds and how many of the window's solves the check samples.

- A closed loop (`"loop": "closed"`, entry `decompose`) is one caller who
  sends the next call to `linalg.decompose` when the last one's factors
  are ready; set-up makes `warmup_calls` calls first.
- An open loop (`"loop": "open"`, entry `service`) is independent clients
  whose calls reach `DecompositionService.submit` on a schedule, whether or
  not earlier calls have resolved: `arrivals` gives the process of the gaps
  ("poisson", or "gamma" with `cv`, the gaps' coefficient of variation)
  and `rate_per_s`; after the window the run waits at most `drain_s` for
  the calls still in flight.

Call i factors pool member i mod pool with a new sketch seed.  An open
loop's arrival times are one fixed draw of its process, the same for every
run; the run seed gives the matrices and the sketch seeds.
"""
from __future__ import annotations

import numbers
import random

import numpy as np

LOOPS = ("closed", "open")
#: the entry each loop's calls go through
ENTRIES = {"closed": "decompose", "open": "service"}
RESIDENCIES = ("device", "host")
PROCESSES = ("poisson", "gamma")
#: the seed of the one draw of arrival times that every run offers
BASE_SEED = 0


def _positive_number(traffic: dict, value, what: str) -> None:
    if (isinstance(value, bool) or not isinstance(value, numbers.Real)
            or not np.isfinite(value) or value <= 0):
        raise ValueError(f"traffic {traffic.get('name')}: {what} must be a "
                         "positive number")


def validate(traffic: dict) -> dict:
    name = traffic.get("name")
    loop = traffic.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"traffic {name}: loop must be one of {LOOPS}")
    entry = traffic.get("entry", "decompose")
    if entry != ENTRIES[loop]:
        raise ValueError(f"traffic {name}: a {loop} loop's entry must be "
                         f"{ENTRIES[loop]!r}, not {entry!r}")
    if traffic.get("residency") not in RESIDENCIES:
        raise ValueError(f"traffic {name}: residency must be one of "
                         f"{RESIDENCIES}")
    counts = ("pool", "warmup_calls", "check_sample") if loop == "closed" else (
        "pool", "check_sample")
    for key in counts:
        if not isinstance(traffic.get(key), int) or traffic[key] < 1:
            raise ValueError(f"traffic {name}: {key} must be a positive integer")
    if loop == "open":
        if traffic["residency"] != "device":
            raise ValueError(f"traffic {name}: an open loop's pool lives on "
                             "the device")
        arrivals = traffic.get("arrivals")
        if not isinstance(arrivals, dict) or arrivals.get("process") not in PROCESSES:
            raise ValueError(f"traffic {name}: arrivals.process must be one of "
                             f"{PROCESSES}")
        _positive_number(traffic, arrivals.get("rate_per_s"), "arrivals.rate_per_s")
        if arrivals["process"] == "gamma":
            _positive_number(traffic, arrivals.get("cv"), "arrivals.cv")
        _positive_number(traffic, traffic.get("drain_s"), "drain_s")
    return traffic


def call(traffic: dict, run_seed: int, i: int) -> tuple[int, int]:
    """(pool member, sketch seed) of call i; set-up's warm-up calls are
    calls 0 .. warmup_calls - 1 and the window goes on from there.  The
    seed stays below 2^31: the streamed engine's fused sketch takes its
    seed as a signed 32-bit integer and raises OverflowError above."""
    return i % traffic["pool"], (run_seed + i) % 2 ** 31


def gaps(arrivals: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """The next n gaps between arrivals, in seconds: exponential for a
    Poisson process, gamma with coefficient of variation `cv` otherwise;
    the mean gap is 1 / rate_per_s either way."""
    mean = 1.0 / arrivals["rate_per_s"]
    if arrivals["process"] == "poisson":
        return rng.exponential(mean, n)
    shape = 1.0 / arrivals["cv"] ** 2
    return rng.gamma(shape, mean / shape, n)


def schedule(traffic: dict, seconds: float) -> np.ndarray:
    """Every arrival time in [0, seconds) of an open loop, in seconds from
    the window's start: one fixed draw of the arrival process (from
    BASE_SEED), the same for every run seed.  A latency tail at a load near
    the knee is set by how the arrivals cluster, which a new draw for each
    seed would change far more than anything the program does."""
    arrivals = traffic["arrivals"]
    rng = np.random.default_rng(BASE_SEED)
    chunk = int(arrivals["rate_per_s"] * seconds) + 64
    times = np.cumsum(gaps(arrivals, rng, chunk))
    while times[-1] < seconds:
        times = np.concatenate([times, times[-1] + np.cumsum(gaps(arrivals, rng, chunk))])
    return times[times < seconds]


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
