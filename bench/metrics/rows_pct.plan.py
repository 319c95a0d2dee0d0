"""Share of the window in sweep result assembly (the per-scenario row
loop): the benchmark's clock around each call, minus the program's build
and solve times."""


def read(run):
    calls = run.records["calls"]
    rest = sum((c["t1"] - c["t0"]) - c["build_s"] - c["solve_s"] for c in calls)
    return 100.0 * rest / run.window_s
