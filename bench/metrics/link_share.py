"""Share of a solve's time that one pass of A over the host link would take,
at the host-to-device bandwidth measured in set-up (a plain `device_put` of
a host buffer of one panel's size).  Only a pool in host memory has it."""


def read(ctx):
    bw = ctx.get("link_bytes_per_s")
    if not bw or not ctx["solves"]:
        return None
    per_solve_s = ctx["window_s"] / len(ctx["solves"])
    return 100.0 * (ctx["pass_bytes"] / bw) / per_solve_s
