"""Real requests per batch the service executed, over the window: the
window's requests over its batches.  A batch of c requests leaves c records
that each say `coalesced` = c, so it counts once as the sum of 1/c over
them; a request that ran alone is a batch of one."""


def read(ctx):
    requests = [r for r in ctx.get("requests") or () if not r.failed]
    if not requests:
        return None
    return len(requests) / sum(1.0 / r.coalesced for r in requests)
