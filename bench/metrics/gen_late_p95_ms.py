"""95th percentile of how late the load generator sent each request due in
the window (send time minus due time): the server loop shares the asyncio
loop, so a host stall shows here first."""
from bench.reduce import p95


def read(rec):
    t0, t1 = rec["window"]
    lates = [r.submit - r.due for r in rec["requests"]
             if r.submit and t0 <= r.due < t1]
    if not lates:
        return None
    return 1e3 * p95(lates)
