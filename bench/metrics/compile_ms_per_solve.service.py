"""compile_ms_per_solve in an open-loop service cell, where it moves
latency_ms_p95: milliseconds that jax spent lowering and compiling inside
the window (its own events, persistent-cache reads included), per request
resolved.  Set-up warms every batch size the service pads to, so anything
here is compiled again by the service or the program itself."""


def read(ctx):
    if not ctx["solves"]:
        return None
    return 1e3 * ctx["compile_s"] / len(ctx["solves"])
