"""Device time of the DP program per ``sweep`` call: the summed
durations of the device ops of every XLA module whose name the trace
prints with ``solve`` in it (the jitted DP entries), over the calls."""

from bench.trace import module_ns

MODULE_KEY = "solve"


def read(run):
    if run.trace is None:
        return None
    ns = module_ns(run.trace, MODULE_KEY)
    if ns == 0:
        return None
    return ns / 1e6 / len(run.records["calls"])
