"""Share of the traced window in which no operation ran on the device:
1 - (union of device-busy intervals) / window, averaged over chips."""


def read(run):
    if run.trace is None or not run.trace["busy_ns"]:
        return None
    busy = [ns / 1e9 for ns in run.trace["busy_ns"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / run.window_s)
