"""The readings the limits of a ``pipeline_loop`` cell are set from.

  python3 bench/pipeline_control.py --workload <cell> --seeds 1,2,3 [--program-seeds ...]

For each seed, at the cell's own size:

* the control (``--seeds``): the plain reference of
  ``bench/reference/pipeline.py`` put in the program's place and computed
  one precision below the configuration's float32, in bfloat16 (stage
  costs, the bottleneck DP and the row's sums), on every sampled row of
  two calls, then compared exactly as a run compares the program;
* the program (``--program-seeds``, on the chip): two calls of the
  program at the cell's load whose comparison covers as many rows.

Each line printed is one JSON object: ``{"who", "seed", "numbers"}``.
The limits lie above the program's largest reading and below the
control's smallest (PERF.md gives both).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

import numpy as np  # noqa: E402

# a control compares as many rows as a run: CONTROL_CALLS calls with the
# run's rows per call times ROWS_FACTOR
CONTROL_CALLS = 2
ROWS_FACTOR = 5


def _traffic(cell, seed: int):
    from bench.drivers.pipeline_loop import Traffic

    t = dict(cell.traffic)
    t["check"] = {"rows_per_call": ROWS_FACTOR * int(
        cell.traffic.get("check", {}).get("rows_per_call", 512))}
    return Traffic(cell.config, t, seed)


def answers(traffic, call: int, dtype) -> dict:
    """Answers for ``call``'s sampled rows, in the layout a run keeps,
    with every number computed in ``dtype``."""
    from bench.drivers.pipeline_loop import _costs
    from bench.reference.pipeline import bottleneck_tables, splits_from

    g = traffic.grids[call]
    cache: dict = {}
    tables: dict = {}
    kept = {}
    for idx in traffic.samples[call]:
        sc = g.scenario(int(idx))
        local, tx, lk = _costs(traffic, call, sc, cache)
        lo, t = local.astype(dtype), tx.astype(dtype)
        key = id(local), id(tx)
        if key not in tables:
            tables[key] = bottleneck_tables(lo + t[None, :], max(g.stages))
        dps, parents = tables[key]
        n, L = sc[2], local.shape[0]
        obj = dps[n - 1, L - 1]
        cuts = splits_from(parents, n, L)
        if not np.isfinite(obj):
            kept[int(idx)] = (sc, (), False, float("inf"), float("inf"),
                              float("inf"), float("inf"))
            continue
        bounds = [0, *cuts, L]
        dev = trans = dtype(0)
        for i in range(n):
            a, b = bounds[i], bounds[i + 1] - 1
            dev = dtype(dev + lo[a, b])
            if b < L - 1:
                trans = dtype(trans + t[b])
        total = dtype(obj + dtype(lk["t_setup_s"]) + dtype(lk["t_feedback_s"]))
        kept[int(idx)] = (sc, cuts, True, float(obj), float(total),
                          float(dev), float(trans))
    return kept


def control(cell, seed: int, dtype) -> dict:
    from bench.drivers.pipeline_loop import compare

    traffic = _traffic(cell, seed)
    got = [(i, answers(traffic, i, dtype)) for i in range(CONTROL_CALLS)]
    return compare(traffic, got, cell.limits)


def program(cell, seed: int) -> dict:
    from bench.drivers.pipeline_loop import Driver

    t = dict(cell.traffic)
    t["check"] = {"rows_per_call": ROWS_FACTOR * int(
        cell.traffic.get("check", {}).get("rows_per_call", 512))}
    d = Driver(cell.config, t, seed)
    d.setup()
    d.window(0.0)  # one call
    d.window(0.0)
    d.free()
    return d.check(cell.limits)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    args = ap.parse_args(argv)

    import ml_dtypes

    from bench.spec import Benchmark

    cell = Benchmark().cell(args.workload)
    if cell.traffic["driver"] != "pipeline_loop":
        sys.exit(f"bench/pipeline_control.py: {args.workload} is not a "
                 f"pipeline_loop cell")

    def emit(who, seed, numbers, t0):
        print(json.dumps({"who": who, "seed": seed, "seconds": time.perf_counter() - t0,
                          "numbers": {k: float(v) for k, v in numbers.items()}}), flush=True)

    for s in (int(x) for x in args.seeds.split(",") if x):
        t0 = time.perf_counter()
        emit("control", s, control(cell, s, ml_dtypes.bfloat16), t0)
    pseeds = [int(x) for x in args.program_seeds.split(",") if x]
    if pseeds:
        import jax

        from bench.spec import enable_cache

        if jax.devices()[0].platform != "tpu":
            sys.exit("bench/pipeline_control.py: the program's readings need a TPU")
        enable_cache()
    for s in pseeds:
        t0 = time.perf_counter()
        emit("program", s, program(cell, s), t0)


if __name__ == "__main__":
    main()
