"""Host time from the call into `decompose` until it returns factors that
are not yet ready: the front end (api, plan cache, planner) and the
engine's host code.  Mean over the solves of the traced window."""


def read(ctx):
    solves = ctx["solves"]
    if not solves:
        return None
    return 1e3 * sum(s["t_return"] - s["t_call"] for s in solves) / len(solves)
