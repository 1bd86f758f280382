#!/usr/bin/env python3
"""The highest arrival rate an open-loop cell's service sustains.

    python3 bench/knee.py --workload <open-loop cell> --seed <n> \
        [--seconds 10] [--start 50] [--precision 0.1]

Builds the cell's pool and service once (set-up as bench/run.py does),
then sends one window of the cell's arrival process at each rate tried,
each followed by its drain.  A rate is sustained where at least 98% of the
window's arrivals resolve before the window closes, none fails, and the
95th percentile of latency stays under 10 times its value at the lowest
rate tried.  The search starts at --start per second and doubles while the
rate is sustained, then bisects until the sustained and the failed rate
are within --precision of each other.  One JSON line per window, then one
with the knee: the highest rate sustained.  The rate a cell offers is
fixed in its traffic file; benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

#: share of a window's arrivals that must resolve before it closes
RESOLVED_SHARE = 0.98
#: how far the p95 latency may rise over its value at the lowest rate tried
P95_RISE = 10.0


def reading(loop, rate: float, seconds: float) -> dict:
    """One window at `rate`, and what it showed."""
    import numpy as np

    import run

    loop.traffic = dict(loop.traffic, arrivals=dict(loop.traffic["arrivals"],
                                                    rate_per_s=rate))
    t0, t1 = loop.window(seconds)
    a = loop.arrivals
    latency_ms = loop.latencies_ms()
    metrics = {name: run._reader(BENCH_DIR / "metrics", name)(loop.ctx())
               for name in ("queue_ms_p50", "coalescing_factor", "execute_ms_per_solve")}
    return {
        "rate_per_s": rate,
        "arrivals": loop.attempted,
        "failed": loop.failed,
        "resolved_in_window": float(np.mean(a.done <= loop.t_close)) if loop.attempted else 0.0,
        "latency_ms_p50": float(np.percentile(latency_ms, 50)),
        "latency_ms_p95": float(np.percentile(latency_ms, 95)),
        "latency_ms_p99": float(np.percentile(latency_ms, 99)),
        "sender_late_ms_p95": float(np.percentile(1e3 * (a.sent - a.due), 95)),
        "last_resolution_s": t1 - t0,
        **metrics,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--start", type=float, default=50.0)
    ap.add_argument("--precision", type=float, default=0.1)
    args = ap.parse_args(argv)

    import jax

    import loadgen
    import openloop
    import run

    spec = run.load_cell(run.ROOT, args.workload)
    cfg, traffic = spec["config"], loadgen.validate(spec["traffic"])
    if traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    run.enable_compile_cache(run.ROOT)
    devices = jax.devices()[:spec["cell"]["chips"]]
    device = devices[0]
    t_setup = time.perf_counter()
    pool = spec["kind"].build_pool(cfg, traffic, args.seed, devices)
    loop = openloop.OpenLoop(pool, cfg, traffic, args.seed)
    print(json.dumps({"device": device.device_kind,
                      "setup_s": time.perf_counter() - t_setup}), flush=True)

    readings = []

    def sustained(rate: float) -> bool:
        r = reading(loop, rate, args.seconds)
        ok = r["failed"] == 0 and r["resolved_in_window"] >= RESOLVED_SHARE
        if readings:
            ok = ok and r["latency_ms_p95"] < P95_RISE * readings[0]["latency_ms_p95"]
        r["sustained"] = ok
        readings.append(r)
        print(json.dumps(r), flush=True)
        return r["sustained"]

    lo, hi = None, args.start
    while sustained(hi):
        lo, hi = hi, 2 * hi
    while lo is not None and (hi - lo) / lo > args.precision:
        mid = (lo + hi) / 2
        if sustained(mid):
            lo = mid
        else:
            hi = mid
    loop.close()
    print(json.dumps({"knee_per_s": lo, "first_rate_not_sustained": hi,
                      "rate_at_0.8": None if lo is None else 0.8 * lo}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
