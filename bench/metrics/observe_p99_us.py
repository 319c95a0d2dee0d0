"""99th percentile of observe latency over every observe offered in the
window: from its due time (open loop) to the return of its handling, on
the benchmark's clock. A shed observe never returns: it counts with the
time it waited until the run ended."""

import numpy as np


def read(run):
    rec = run.records
    lat = [x for x in rec["latencies"] if x[0] < rec["seconds"]]
    if not lat:
        return None
    end = max(done for _, done in rec["latencies"] if done is not None)
    vals = [(done if done is not None else end) - due for due, done in lat]
    return float(np.percentile(vals, 99.0)) * 1e6
