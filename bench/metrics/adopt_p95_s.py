"""95th percentile, over every session drifted in the window, of the time
from the due time of its first drifted observe to the return of the
first observe that its surface answered (the end of stale serving: a
rebuilt surface covering the drifted state has been adopted). A session
that never adopted counts with the time it waited until the run ended."""

import numpy as np


def read(run):
    rec = run.records
    drifts = rec["drifts"].values()
    if not drifts:
        return None
    end = max(done for _, done in rec["latencies"] if done is not None)
    vals = [(d["adopted"] if d["adopted"] is not None else end) - d["first_due"]
            for d in drifts]
    return float(np.percentile(vals, 95.0))
