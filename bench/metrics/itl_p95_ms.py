"""95th percentile of every client-side gap between consecutive tokens of
the requests due in the window.  A request cut at the grace limit adds one
gap from its last token (or its due time) to the cut."""
import numpy as np

from bench.reduce import cutoff, p95


def read(rec):
    end = cutoff(rec)
    gaps = []
    for r in rec["requests"]:
        gaps.extend(np.diff(r.recv))
        if not r.done:
            gaps.append(end - (r.recv[-1] if r.recv else r.due))
    return 1e3 * p95(gaps) if gaps else None
