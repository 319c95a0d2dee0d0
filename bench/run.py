"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout on a machine with the chips the cell asks
for (``BENCHMARK.json``). The cell's traffic file names its driver; the
driver sets up the system (warm-up included), runs the measured window,
and after the window compares what the timed path produced with the
plain reference. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
the window's records and its device trace.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, optionally
``breakdown``, and last ``checks``: every number compared beside its
limit); the same numbers close standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout root (for ``bench.*``) and the program's sources; the
# script's own directory would shadow standard modules (``trace``)
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
# the compile cache lives at a fixed path inside the checkout, and the
# program takes this one (its helper keeps the variable when it is set)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Run:
    """What a metric reader sees: the driver's records, the window, the
    reduced trace (``None`` with ``--trace 0``) and the device."""

    def __init__(self, cell, records, window_s, trace, device_kind, compiles):
        self.cell = cell
        self.records = records
        self.window_s = window_s
        self.trace = trace
        self.device_kind = device_kind
        self.compiles = compiles


def devices_or_exit(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        sys.exit(f"bench/run.py: needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform} device(s) "
                 f"({devs[0].device_kind}); there is no CPU fallback")
    return devs


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def run(argv=None, *, require_tpu: bool = True, traffic_overrides=None,
        root: Path = ROOT, out=sys.stdout, err=sys.stderr) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import Benchmark

    cell = Benchmark(root).cell(args.workload)
    if traffic_overrides:
        traffic_overrides(cell.traffic)
    devs = devices_or_exit(cell.chips, require_tpu)
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}", file=err)

    import jax

    from bench.spec import enable_cache

    enable_cache()

    compile_times: list[float] = []

    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            compile_times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    driver = cell.driver().Driver(cell.config, cell.traffic, args.seed)
    driver.setup()
    setup_s = time.perf_counter() - T_START

    from bench import trace as tr

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        if args.trace:
            with tr.Tracer(tmp) as tracer:
                start, end = driver.window(args.seconds)
            reduced = tr.reduce_xplane(tracer.xplane())
        else:
            start, end = driver.window(args.seconds)
            reduced = None
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    window_s = end - start
    compiles = sum(1 for t in compile_times if start <= t <= end)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    records = driver.records()
    attempted, failed = driver.attempted()
    driver.free()

    numbers = driver.check(cell.limits)
    checks = {k: {"value": _finite(v), "limit": cell.limits[k]}
              for k, v in numbers.items()}
    correct = all(k in cell.limits and v <= cell.limits[k]
                  for k, v in numbers.items())

    run_info = Run(cell.name, records, window_s, reduced, dev.device_kind,
                   compiles)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    for name, (m, reader) in cell.readers(kind).items():
        value = reader.read(run_info)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        busy = [ns / 1e9 for ns in reduced["busy_ns"].values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = window_s
        result["breakdown"] = tr.breakdown(reduced)
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=err)
    print(json.dumps(result), file=out, flush=True)
    return result


if __name__ == "__main__":
    run()
