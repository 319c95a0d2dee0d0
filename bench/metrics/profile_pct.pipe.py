"""Share of the window spent building the pipeline what-if grid on the
host (``planner.pipeline_grid``: every shape's layer graph and TPU cost
profile): the benchmark's clock around the call, summed over the
window's calls."""


def read(run):
    return 100.0 * sum(c["profile_s"] for c in run.records["calls"]) / run.window_s
