"""The service's execute walltime (`RequestRecord.execute_s`: the solve of
one batch, from its call until its factors are ready), each batch counted
once, over the window's requests."""


def read(ctx):
    requests = [r for r in ctx.get("requests") or () if not r.failed]
    if not requests:
        return None
    return 1e3 * sum(r.execute_s / r.coalesced for r in requests) / len(requests)
