"""Planner-tier scale benchmark: PlanSpec-resolved solves at fleet scale.

Pushes S scenarios (10^5 in full mode) through ``PlannerService`` as ONE
spec-resolved batched solve and reports:

* **solve** — spec-path throughput (scenarios/s, us/scenario) of the
  planner tier end-to-end (spec construction + dispatch + batched DP).
* **serialization** — what the serializable contract costs: bytes of a
  fully-loaded surface spec (cost model + protocol bank + variant
  bank), wall time of a ``to_json``/``from_json`` round trip, and
  that overhead as a percentage of the solve itself (it is noise — the
  spec is O(model), the solve is O(S)). ``roundtrip_exact`` asserts the
  round trip is field-exact, non-finite floats included.
* **parity** — the spec path vs the kwargs path on the same
  tensor, asserted bitwise identical (same splits, costs, feasibility).
* **rebuild** — a surface rebuild driven through ``FleetGateway``'s
  rebuilder twice: in-process (the spec resolved on this process) vs
  out-of-process (the spec pickled to a spawned, CPU-pinned
  ``ProcessPoolExecutor`` worker via
  ``repro.core.spec.build_surfaces_from_spec``). The pool wall
  includes worker spawn + import — the honest cold-start cost of the
  process boundary, which is why the gate checks the parity flags, not
  the ratio. ``pool_parity_ok`` asserts the adopted surface is
  node-identical to the synchronous build; ``zero_stale_adoptions``
  audits the handle's generation trail.

Usage:
  PYTHONPATH=src python benchmarks/planner_scale.py           # full (S=100000)
  PYTHONPATH=src python benchmarks/planner_scale.py --smoke   # CI (S=2000)
  ... [--json BENCH_planner.json]

The JSON artifact (``BENCH_planner.json``) is the committed baseline
``tools/check_bench.py --planner`` gates CI smoke runs against.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.async_replan import cpu_process_pool
from repro.core.profiles import (
    ESP_NOW,
    PROTOCOLS,
    esp32_variant_bank,
    paper_cost_model,
)
from repro.core.spec import (
    PlannerService,
    PlanSpec,
    surfaces_spec,
    tensor_spec,
)
from repro.core.sweep import solve_batched
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.gateway import FleetGateway

SMOKE_S, FULL_S = 2_000, 100_000
N, L = 3, 8
GRID = {"pt_scale": (1.0, 4.0, 16.0), "loss_p": (0.0, 0.1)}
NBYTES = 5488


def _cost_tensor(S: int, seed: int = 0) -> np.ndarray:
    """Random stacked ``(S, N, L, L)`` cost tensor with the solver's
    invalid-entry convention plus a sprinkle of infeasible entries —
    structurally what ``stack_cost_tensors`` emits, sized freely."""
    rng = np.random.default_rng(seed)
    C = rng.uniform(0.1, 9.0, size=(S, N, L, L))
    C[rng.uniform(size=C.shape) < 0.05] = np.inf
    idx = np.arange(1, L + 1)
    C[:, :, idx[:, None] > idx[None, :]] = np.inf
    return C


def _results_identical(a, b) -> bool:
    return (np.array_equal(a.splits, b.splits)
            and np.array_equal(a.cost_s, b.cost_s)
            and np.array_equal(a.feasible, b.feasible))


def _surfaces_identical(a, b) -> bool:
    if sorted(a.protocols) != sorted(b.protocols):
        return False
    for name in a.protocols:
        pa, pb = a.protocols[name], b.protocols[name]
        if not (pa.packet_time_s == pb.packet_time_s
                and pa.loss_p == pb.loss_p
                and np.array_equal(pa.splits, pb.splits)
                and np.array_equal(pa.chunk_bytes, pb.chunk_bytes)
                and np.array_equal(pa.latency_s, pb.latency_s)):
            return False
    return True


def _rich_spec() -> PlanSpec:
    """A fully-loaded surface spec — the serialization worst case."""
    return surfaces_spec(
        paper_cost_model("mobilenet_v2", "esp_now"), PROTOCOLS, (2, 3, 5),
        pt_scale=(1.0, 2.0, 4.0, 8.0, 16.0), loss_p=(None, 0.0, 0.05, 0.1),
        chunk_candidates=(256, 1024, 4096), energy_budget=float("inf"),
        variants=esp32_variant_bank(), accuracy_floor=0.9)


def _solve_and_parity(S: int) -> tuple[dict, dict, float]:
    C = _cost_tensor(S)
    n = tuple(2 + (s % (N - 1)) for s in range(S))  # mixed fleet sizes
    service = PlannerService()
    t0 = time.perf_counter()
    spec = tensor_spec(C, combine="sum", n_devices=n)
    via_spec = service.solve(spec, C)
    wall = time.perf_counter() - t0
    via_kwargs = solve_batched(C, n_devices=n)
    solve = {
        "n_scenarios": S, "n_devices_max": N, "layers": L,
        "wall_s": round(wall, 4),
        "scenarios_per_sec": round(S / wall, 1),
        "us_per_scenario": round(wall * 1e6 / S, 3),
    }
    parity = {
        "backend": "numpy",
        "spec_path_identical": _results_identical(via_spec, via_kwargs),
    }
    return solve, parity, wall


def _serialization(solve_wall_s: float, repeats: int = 200) -> dict:
    spec = _rich_spec()
    payload = spec.to_json()
    t0 = time.perf_counter()
    for _ in range(repeats):
        again = PlanSpec.from_json(spec.to_json())
    rt = (time.perf_counter() - t0) / repeats
    return {
        "spec_bytes": len(payload),
        "roundtrip_us": round(rt * 1e6, 1),
        "overhead_pct_of_solve": round(100.0 * rt / solve_wall_s, 4),
        "roundtrip_exact": again == spec,
    }


def _rebuild() -> dict:
    model = paper_cost_model("mobilenet_v2", "esp_now")
    pool = cpu_process_pool()
    gw = FleetGateway(model, PROTOCOLS, (2, 3), surface_grid=GRID,
                      executor=pool)
    try:
        pt = 24.0 * ESP_NOW.transmission_latency_s(NBYTES)
        states = {name: (pt, 0.05) for name in PROTOCOLS}
        gw.rebuilder.request(2, states)
        handle = gw.fanout.view()
        t0 = time.perf_counter()
        got, deadline = None, time.monotonic() + 300.0
        while got is None and time.monotonic() < deadline:
            got = handle.poll(2)  # first poll launches on the pool
            if got is None:
                time.sleep(0.01)
        pool_wall = time.perf_counter() - t0
        if got is None:
            raise RuntimeError("process-pool rebuild never adopted")
        req = gw.rebuilder.last_request
        t0 = time.perf_counter()
        sync = gw.rebuilder.build_sync(req)
        in_wall = time.perf_counter() - t0
        gens = [g for (n, g) in handle.adoptions if n == 2]
        return {
            "in_process_wall_s": round(in_wall, 4),
            "process_pool_wall_s": round(pool_wall, 4),
            "pool_over_inprocess_x": round(pool_wall / in_wall, 2),
            "pool_parity_ok": _surfaces_identical(got, sync[2]),
            "zero_stale_adoptions": gens == sorted(set(gens)),
            "builds_completed": gw.rebuilder.builds_completed,
        }
    finally:
        gw.rebuilder.shutdown()
        pool.shutdown(wait=True)


def run(smoke: bool = True) -> dict:
    S = SMOKE_S if smoke else FULL_S
    solve, parity, wall = _solve_and_parity(S)
    return {
        "benchmark": "planner_scale",
        "mode": "smoke" if smoke else "full",
        "solve": solve,
        "serialization": _serialization(wall),
        "parity": parity,
        "rebuild": _rebuild(),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help=f"CI-sized run (S={SMOKE_S} vs {FULL_S})")
    ap.add_argument("--json", default="BENCH_planner.json",
                    help="path for the machine-readable result "
                         "(empty to skip)")
    args = ap.parse_args()
    enable_compile_cache()

    print("\n=== planner_scale: PlanSpec-resolved solves at fleet scale ===")
    report = run(smoke=args.smoke)
    sv, ser, pa, rb = (report["solve"], report["serialization"],
                       report["parity"], report["rebuild"])
    print(f"solve: {sv['n_scenarios']} scenarios in {sv['wall_s']}s "
          f"-> {sv['scenarios_per_sec']} scenarios/s "
          f"({sv['us_per_scenario']} us/scenario)")
    print(f"serialization: {ser['spec_bytes']} B spec, round trip "
          f"{ser['roundtrip_us']} us ({ser['overhead_pct_of_solve']}% of "
          f"the solve), exact: {ser['roundtrip_exact']}")
    print(f"parity: spec path bitwise == kwargs path "
          f"({pa['backend']}): {pa['spec_path_identical']}")
    print(f"rebuild: in-process {rb['in_process_wall_s']}s vs process pool "
          f"{rb['process_pool_wall_s']}s (incl. spawn; "
          f"{rb['pool_over_inprocess_x']}x), pool parity: "
          f"{rb['pool_parity_ok']}, zero stale adoptions: "
          f"{rb['zero_stale_adoptions']}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json}")

    if not (ser["roundtrip_exact"] and pa["spec_path_identical"]
            and rb["pool_parity_ok"] and rb["zero_stale_adoptions"]):
        raise SystemExit("planner_scale: correctness check failed")


if __name__ == "__main__":
    main()
