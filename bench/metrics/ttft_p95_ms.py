"""95th percentile over the requests due in the record's window of the time
from when each was due to when its client received its first token.  A
request that never got one (shed, or cut at the grace limit) counts until
the cut.  In a traced run the window is the traced stretch, as for the
other per-layer metrics."""
from bench.reduce import cutoff, p95


def read(rec):
    t0, t1 = rec["window"]
    reqs = [r for r in rec["requests"] if t0 <= r.due < t1]
    if not reqs:
        return None
    end = cutoff(rec)
    return 1e3 * p95([(r.recv[0] if r.recv else end) - r.due for r in reqs])
