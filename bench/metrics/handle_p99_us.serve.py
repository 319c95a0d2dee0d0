"""99th percentile of the gateway's own observe handling time (its
``QosMonitor`` fleet window, sized to hold every observe of the
window): the surface lookup and adoption inside ``pump``, without the
queueing before it."""

import numpy as np


def read(run):
    vals = run.records["handle_s"]
    if not vals:
        return None
    return float(np.percentile(vals, 99.0)) * 1e6
