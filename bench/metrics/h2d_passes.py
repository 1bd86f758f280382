"""Passes of A over the host link per solve: the bytes the program put on
the device (its `pipeline.h2d_bytes` counter, staging padding included)
over its solves (`linalg.solves`), in units of one pass of A.

The counters are the program's own, process-wide, read after the window;
every call of the run, set-up's warm-up included, is the same solve of a
matrix of the same shape, so the ratio is the window's.  A program without
the counters (before linalg/trace.py) reads nothing."""


def read(ctx):
    try:
        from repro.linalg import trace
    except ImportError:
        return None
    counters = trace.counters()
    solves = counters.get("linalg.solves")
    put = counters.get("pipeline.h2d_bytes")
    if not solves or put is None:
        return None
    return put / solves / ctx["pass_bytes"]
