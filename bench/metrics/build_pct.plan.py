"""Share of the window spent building the sweep grid on the host (profile
bank, transmission vectors, ``bank_idx``): the program's own
``SweepResult.build_time_s``, summed over the window's calls."""


def read(run):
    return 100.0 * sum(c["build_s"] for c in run.records["calls"]) / run.window_s
