"""Scenarios planned per second: every scenario of every ``sweep`` call
in the window over the wall time from window start to the end of the
last call (host clock; calls run back to back)."""


def read(run):
    calls = run.records["calls"]
    return sum(c["scenarios"] for c in calls) / run.window_s
