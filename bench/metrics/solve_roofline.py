"""Share of the roofline: the least time of one solve's required work
(work.py, from the configuration's sizes alone) at the chip's published
peaks, over the device busy time per solve (the busy union of the traced
window over the solves in it), per chip and averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["busy_s"] <= 0 or not ctx["solves"]:
        return None
    busy_per_solve = trace["busy_s"] / len(ctx["solves"])
    return 100.0 * ctx["least_time_s"] / busy_per_solve
