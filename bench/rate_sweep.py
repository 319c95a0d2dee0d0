"""Find the highest observe rate a gateway cell sustains, on the chip.

  python3 bench/rate_sweep.py --workload mnv2-gateway \
      --rates 8000,12000,16000 [--seconds 10] [--seeds 1,2] [--p99-limit-ms 50]

Runs the cell's driver once per rate and seed (a fresh gateway each
time, in this one process) with the traffic file's ``rate_per_s``
replaced, and prints one JSON line per run: the observe latency
percentiles, the median latency of the observes due in each quarter of
the window, whether the queue held (after the first quarter, which
holds the drift burst, no quarter's median is more than twice another's
and each is under 50 ms: the burst's backlog drained and the queue does
not grow) and whether p99 met the limit. The limit defaults to the
drift-report period (50 ms): a slower answer reaches a drifted session
after its next report. The last line names the highest rate that held
and met the limit on every seed; the cell's rate is set, in its traffic
file, to four fifths of it (PERF.md records the sweep)."""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

import numpy as np  # noqa: E402

HELD_RATIO = 2.0
HELD_MEDIAN_S = 0.05


def one_rate(cell, rate: float, seconds: float, seed: int) -> dict:
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["rate_per_s"] = rate
    d = cell.driver().Driver(cell.config, traffic, seed)
    d.setup()
    d.window(seconds)
    lat = [(due, done) for due, done in d.last["latencies"] if due < seconds]
    end = max(done for _, done in d.last["latencies"] if done is not None)
    vals = np.array([(done if done is not None else end) - due for due, done in lat])
    dues = np.array([due for due, _ in lat])
    quarters = [float(np.median(vals[(dues >= q * seconds / 4) & (dues < (q + 1) * seconds / 4)]))
                for q in range(4)]
    d.free()
    rest = quarters[1:]
    held = max(rest) <= HELD_RATIO * min(rest) and max(rest) < HELD_MEDIAN_S
    return {"rate_per_s": rate, "seed": seed, "offered": len(lat), "failed": int(d.last["failed"]),
            "p50_us": float(np.percentile(vals, 50)) * 1e6,
            "p99_us": float(np.percentile(vals, 99)) * 1e6,
            "quarter_median_us": [q * 1e6 for q in quarters], "held": bool(held)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--p99-limit-ms", type=float, default=50.0)
    args = ap.parse_args(argv)

    import jax

    from bench.spec import Benchmark, enable_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench/rate_sweep.py: needs a TPU; JAX found {dev.platform}")
    enable_cache()
    cell = Benchmark().cell(args.workload)
    best = None
    for rate in (float(r) for r in args.rates.split(",")):
        ok = True
        for seed in (int(s) for s in args.seeds.split(",")):
            row = one_rate(cell, rate, args.seconds, seed)
            row["met"] = row["p99_us"] <= args.p99_limit_ms * 1e3
            ok = ok and row["held"] and row["met"]
            print(json.dumps(row), flush=True)
        if ok:
            best = rate
    print(json.dumps({"highest_sustained_per_s": best}), flush=True)


if __name__ == "__main__":
    main()
