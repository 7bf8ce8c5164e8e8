"""Median over the requests due in the window of the time from when each was
due to when its client received its first token.  A request that never got
one (shed, or cut at the grace limit) counts until the cut."""
import numpy as np

from bench.reduce import cutoff


def read(rec):
    if not rec["requests"]:
        return None
    end = cutoff(rec)
    return 1e3 * float(np.median([(r.recv[0] if r.recv else end) - r.due
                                  for r in rec["requests"]]))
