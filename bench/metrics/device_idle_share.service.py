"""device_idle_share in an open-loop service cell, where it moves
latency_ms_p95: 1 - device busy time over the traced window (the sending
and the drain), from the profiler trace, averaged over the cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * trace["idle_share"]
