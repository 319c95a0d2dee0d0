"""The control readings of a ``sweep_loop`` cell with energy budgets.

  python3 bench/energy_control.py --workload r50-energy-whatif --seeds 1,2,3

``bench/control.py`` prices unbudgeted grids only. For each seed, at the
cell's own grid size, this puts the plain reference of
``bench/reference`` (``costmodel`` and ``dp``) in the program's place and
computes it one precision below the configuration's float32, in
bfloat16 on the host (``ml_dtypes``): the device-local latencies, the
airtime, every segment's energy and its mask against the budget, the DP
and each row's sums. Every sampled row of two calls (five times a run's
rows per call) is then compared by ``sweep_loop.compare`` under the
cell's limits, as a run compares the program. The limits lie above the
program's largest reading and below the control's smallest (PERF.md
gives both).

Each line printed is one JSON object: ``{"who", "seed", "numbers"}``.
No chip is needed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

# a control compares as many answers as a run: CONTROL_CALLS calls with
# the run's rows per call times ROWS_FACTOR
CONTROL_CALLS = 2
ROWS_FACTOR = 5


def _traffic(cell, seed: int):
    from bench.drivers.sweep_loop import Traffic

    t = dict(cell.traffic)
    t["check"] = {"rows_per_call": ROWS_FACTOR * int(
        cell.traffic.get("check", {}).get("rows_per_call", 512))}
    return Traffic(cell.config, t, seed)


def answers(traffic, call: int, dtype) -> dict:
    """Answers for ``call``'s sampled rows, in the layout a run keeps,
    with every number computed in ``dtype``."""
    from bench.reference import dp as refdp

    dep, dev_cfg = traffic.dep, traffic.device
    g = traffic.grids[call]
    L = dep.L
    inf = np.array(np.inf, dtype=dtype)
    first = dep.local_matrix(True, dev_cfg).astype(dtype)
    rest = dep.local_matrix(False, dev_cfg).astype(dtype)
    p_active = dtype(dev_cfg.get("active_power_w", 0.0))

    # one DP per distinct (link, budget) among the sampled rows
    combos: dict = {}
    for idx in traffic.samples[call]:
        n, p, loss, rate, con, budget, _ = g.scenario(int(idx))
        combos.setdefault((p, loss, rate, con, budget), len(combos))
    tx_rows, first_rows, masks = [], [], []
    for (p, loss, rate, con, budget) in combos:
        lk = dep.link(p, loss, rate, con, traffic.mac_efficiency)
        tx = dep.airtime(lk).astype(dtype)
        fr = first[0] + tx
        er = np.zeros((L, L), dtype=bool)
        if budget is not None:
            air_in = np.zeros(L, dtype=dtype)
            air_in[1:] = tx[:-1]
            side = (dtype(lk.get("tx_power_w", 0.0)) * tx[None, :]
                    + dtype(lk.get("rx_power_w", 0.0)) * air_in[:, None])
            cap = dtype(budget)
            fr = np.where(p_active * first[0] + side[0] > cap, inf, fr)
            er = p_active * rest + side > cap
        tx_rows.append(tx)
        first_rows.append(fr)
        masks.append(er)
    tx_all = np.stack(tx_rows)
    seg = np.where(np.stack(masks), inf, rest[None] + tx_all[:, None, :])
    dps, parents = refdp.tables(np.stack(first_rows), lambda k: seg,
                                max(g.n_devices))

    kept = {}
    for idx in traffic.samples[call]:
        sc = g.scenario(int(idx))
        n, p, loss, rate, con, budget, _ = sc
        r = combos[(p, loss, rate, con, budget)]
        obj = dps[r, n - 1, L - 1]
        splits = tuple(int(s) for s in refdp.splits_from(
            parents[r:r + 1], np.array([n]), L)[0])
        if not np.isfinite(obj) or any(s <= 0 for s in splits):
            kept[int(idx)] = (sc, (), False, float("inf"), float("inf"),
                              float("inf"), float("inf"))
            continue
        lk = dep.link(p, loss, rate, con, traffic.mac_efficiency)
        bounds = [0, *splits, L]
        dev = trans = dtype(0)
        for k in range(n):
            m = first if k == 0 else rest
            dev = dtype(dev + m[bounds[k], bounds[k + 1] - 1])
            if bounds[k + 1] < L:
                trans = dtype(trans + tx_all[r, bounds[k + 1] - 1])
        total = dtype(obj + dtype(lk["t_setup_s"]) + dtype(lk["t_feedback_s"]))
        kept[int(idx)] = (sc, splits, True, float(obj), float(total),
                          float(dev), float(trans))
    return kept


def control(cell, seed: int, dtype) -> dict:
    from bench.drivers.sweep_loop import compare

    traffic = _traffic(cell, seed)
    got = [(i, answers(traffic, i, dtype)) for i in range(CONTROL_CALLS)]
    return compare(traffic, got, cell.limits)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)

    import ml_dtypes

    from bench.spec import Benchmark

    cell = Benchmark().cell(args.workload)
    grid = cell.traffic.get("grid", {})
    if cell.traffic["driver"] != "sweep_loop" or not any(
            b is not None for b in grid.get("energy_budgets", [None])):
        sys.exit(f"bench/energy_control.py: {args.workload} is not a "
                 f"sweep_loop cell with energy budgets")
    for s in (int(x) for x in args.seeds.split(",") if x):
        t0 = time.perf_counter()
        numbers = control(cell, s, ml_dtypes.bfloat16)
        print(json.dumps({"who": "control", "seed": s,
                          "seconds": time.perf_counter() - t0,
                          "numbers": {k: float(v) for k, v in numbers.items()}}),
              flush=True)


if __name__ == "__main__":
    main()
