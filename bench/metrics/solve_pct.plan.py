"""Share of the window in the DP dispatch (padding, transfer, kernel,
split reconstruction): the program's ``SweepResult.solve_time_s``,
summed over the window's calls."""


def read(run):
    return 100.0 * sum(c["solve_s"] for c in run.records["calls"]) / run.window_s
