"""The readings that the limits of ``bench/checks/<cell>.json`` are set from.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 [--program-seeds ...]

For each seed, on the chip, at the cell's own size:

* the control: the plain reference put in the program's place and
  computed one precision below the configuration's float32, in
  bfloat16 on the device (``sweep_loop``: every scenario of each call's
  grid; ``gateway_open_loop``: every surface family the run adopted,
  rebuilt by the reference), then compared exactly as a run compares
  the program;
* the program (``--program-seeds``): a short window at the cell's load
  whose comparison covers as many answers as a run's.

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

# a control compares as many answers as a run: CONTROL_CALLS calls with
# the run's rows per call times this factor
CONTROL_CALLS = 2
ROWS_FACTOR = 5


# ---------------------------------------------------------------------------
# sweep_loop: the reference DP in the program's place
# ---------------------------------------------------------------------------


def sweep_answers(traffic, call: int, dtype, xp=np) -> dict:
    """Answers for ``call``'s sampled rows, in the layout a run keeps:
    the reference DP over every scenario of the call's grid, computed
    in ``dtype`` with ``xp``."""
    from bench.reference import dp as refdp

    dep = traffic.dep
    g = traffic.grids[call]
    if any(b is not None for b in g.budgets):
        raise ValueError("the control prices unbudgeted grids only")
    first = dep.local_matrix(True, traffic.device)
    rest = dep.local_matrix(False, traffic.device)
    links = {}
    rows = []
    for p in g.protocols:
        for loss in g.loss_p:
            for rate in g.rate_scale:
                for con in g.contention:
                    lk = dep.link(p, loss, rate, con, traffic.mac_efficiency)
                    links[(p, loss, rate, con)] = len(rows)
                    rows.append(dep.airtime(lk))
    tx = np.stack(rows)
    n_max = max(g.n_devices)
    tx_d = xp.asarray(tx, dtype=dtype)
    first_d = xp.asarray(first, dtype=dtype)
    rest_d = xp.asarray(rest, dtype=dtype)
    fr = first_d[0][None, :] + tx_d
    dps, parents = refdp.tables(fr, lambda k: rest_d[None] + tx_d[:, None, :], n_max, xp=xp)
    dps = np.asarray(dps.astype(xp.float32))
    parents = np.asarray(parents)
    kept = {}
    for idx in traffic.samples[call]:
        sc = g.scenario(int(idx))
        n, p, loss, rate, con, budget, _ = sc
        r = links[(p, loss, rate, con)]
        obj = float(dps[r, n - 1, -1])
        splits = tuple(int(s) for s in refdp.splits_from(
            parents[r:r + 1], np.array([n]), dep.L)[0])
        feasible = np.isfinite(obj) and all(s > 0 for s in splits)
        lk = dep.link(p, loss, rate, con, traffic.mac_efficiency)
        if feasible:
            bounds = [0, *splits, dep.L]
            dev = dtype(0)
            trans = dtype(0)
            for k in range(n):
                m = first if k == 0 else rest
                dev = dtype(dev + dtype(m[bounds[k], bounds[k + 1] - 1]))
                if bounds[k + 1] < dep.L:
                    trans = dtype(trans + dtype(tx[r, bounds[k + 1] - 1]))
            total = dtype(dtype(obj) + dtype(lk["t_setup_s"]) + dtype(lk["t_feedback_s"]))
            kept[int(idx)] = (sc, splits, True, obj, float(total), float(dev), float(trans))
        else:
            kept[int(idx)] = (sc, (), False, float("inf"), float("inf"),
                              float("inf"), float("inf"))
    return kept


def sweep_control(cell, seed: int, xp, dtype) -> dict:
    from bench.drivers.sweep_loop import Traffic, compare

    t = dict(cell.traffic)
    t["check"] = {"rows_per_call": ROWS_FACTOR * int(
        cell.traffic.get("check", {}).get("rows_per_call", 512))}
    traffic = Traffic(cell.config, t, seed)
    answers = [(i, sweep_answers(traffic, i, dtype, xp)) for i in range(CONTROL_CALLS)]
    return compare(traffic, answers, cell.limits)


def sweep_program(cell, seed: int) -> dict:
    from bench.drivers.sweep_loop import Driver

    t = dict(cell.traffic)
    t["check"] = {"rows_per_call": ROWS_FACTOR * int(
        cell.traffic.get("check", {}).get("rows_per_call", 512))}
    d = Driver(cell.config, t, seed)
    d.setup()
    d.window(0.0)  # one call
    d.window(0.0)
    d.free()
    return d.check(cell.limits)


# ---------------------------------------------------------------------------
# gateway_open_loop: reference surface builds in the program's place
# ---------------------------------------------------------------------------


def gateway_control(cell, seed: int, seconds: float, dtype) -> tuple[dict, dict]:
    """(program numbers, control numbers) of one short run: the control
    rebuilds every adopted request with the reference in ``dtype`` and
    is compared like the program's surfaces."""
    from bench.reference.surface import SurfaceReference

    d = cell.driver().Driver(cell.config, cell.traffic, seed)
    d.setup()
    d.window(seconds)
    d.free()
    program = d.check(cell.limits)
    import jax.numpy as jnp

    low = SurfaceReference(d.dep, dtype=dtype, xp=jnp)
    control = {}
    for gen, fam in d.families.items():
        _, pt_scale, loss_p, sizes = d.requests[gen]
        sizes = [n for n in sizes if n in fam]
        built = low.build(pt_scale, loss_p, sizes)
        got = d.ref.compare(built, pt_scale, loss_p, sizes)
        for k, v in got.items():
            control[k] = max(control.get(k, 0), v) if isinstance(v, float) \
                else control.get(k, 0) + v
    return program, control


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="gateway window per seed")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench.spec import Benchmark, enable_cache

    cell = Benchmark().cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench/control.py: needs a TPU; JAX found {dev.platform}")
    enable_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    pseeds = [int(s) for s in args.program_seeds.split(",") if s]
    driver = cell.traffic["driver"]

    def emit(who, seed, numbers, t0):
        print(json.dumps({"who": who, "seed": seed, "seconds": time.perf_counter() - t0,
                          "numbers": {k: float(v) for k, v in numbers.items()}}), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        if driver == "sweep_loop":
            emit("control", seed, sweep_control(cell, seed, jnp, jnp.bfloat16), t0)
        else:
            program, control = gateway_control(cell, seed, args.seconds, jnp.bfloat16)
            emit("program", seed, program, t0)
            emit("control", seed, control, t0)
    for seed in pseeds:
        t0 = time.perf_counter()
        if driver == "sweep_loop":
            emit("program", seed, sweep_program(cell, seed), t0)
        else:
            program, _ = gateway_control(cell, seed, args.seconds, jnp.bfloat16)
            emit("program", seed, program, t0)


if __name__ == "__main__":
    main()
