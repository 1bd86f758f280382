"""solve_roofline in an open-loop service cell, where it moves
latency_ms_p95: the least time of one request's required work (work.py,
from the configuration's sizes alone) at the chip's published peaks, over
the device busy time per request resolved (the busy union of the traced
window, batching and padding included, over the requests)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["busy_s"] <= 0 or not ctx["solves"]:
        return None
    busy_per_solve = trace["busy_s"] / len(ctx["solves"])
    return 100.0 * ctx["least_time_s"] / busy_per_solve
