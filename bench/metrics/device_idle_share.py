"""1 - device busy time over the traced window, from the profiler trace,
averaged over the cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * trace["idle_share"]
