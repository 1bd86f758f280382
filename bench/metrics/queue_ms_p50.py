"""The service's own queue wait, from `submit` to the start of execution
(`RequestRecord.queue_s`: the admission window and the wait for the
device), median over the window's requests that resolved."""
import statistics


def read(ctx):
    waits = [r.queue_s for r in ctx.get("requests") or () if not r.failed]
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
