"""Fleet-scale scenario sweep benchmark — the vectorized engine vs the
scalar per-scenario loop.

Sweeps a protocol × fleet-size × loss-rate × bandwidth (× model) grid
with the batched DP (one array pass per (model, N) group) and with the
scalar ``optimal_dp`` loop it replaces, verifies bit-identical best
splits, and reports scenarios/sec + speedup.

Usage:
  PYTHONPATH=src python benchmarks/sweep_grid.py            # full grid (512 scenarios)
  PYTHONPATH=src python benchmarks/sweep_grid.py --smoke    # CI smoke (256 scenarios)
  ... [--backend jax|sharded] [--json BENCH_sweep.json] [--csv sweep.csv]
  ... [--sections sharded,pallas,multichannel,frontier]  # limit the extra sections

The report always carries a ``sharded`` section — the same grid solved
with the scenario axis partitioned over every local JAX device
(``repro.core.shard``), asserted node-identical to the single-device
JAX path — and a ``pallas`` section: the grid solved by the fused
cost-construction + DP kernel (``repro.core.pallas_dp``,
``backend="pallas"``), which never materializes the ``C[S, N, L, L]``
tensor. The pallas section asserts every node matches the JAX path
exactly OR is an exact-cost tie (zero float64-repriced regret — the
fused construction rounds <=1 ulp differently, so exact ties may break
toward a different equally-optimal plan; see the pallas_dp module
docstring). Off-TPU the kernel runs in interpret mode: the recorded
wall times exercise the Pallas *interpreter* and assert correctness
only — the >=10x fusion target is a real-accelerator claim.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
CI ``multi-device`` job does) to exercise a real mesh for the sharded
section; on a plain host it degenerates to one shard. All JAX-side
paths are warmed up before timing so the recorded walls are
steady-state (compile excluded), per the
``BatchedSolverResult.wall_time_s`` comparability contract.

The JSON artifact (``BENCH_sweep.json`` by default) is the
machine-readable perf record future PRs compare against
(``tools/check_bench.py`` gates CI smoke runs on it).
"""

from __future__ import annotations

import argparse
import json
import math
import time

from dataclasses import replace

import numpy as np

from repro.core.latency import COST_CHANNELS
from repro.core.profiles import ESP32, PROTOCOLS, mobilenet_cost_profile, paper_cost_model, resnet50_cost_profile
from repro.core.sweep import (
    ScenarioGrid,
    parity_report,
    solve_batched,
    solve_multi_channel,
    stack_cost_tensors,
    sweep,
    sweep_scalar,
)
from repro.launch.compile_cache import enable_compile_cache

LOSS_P = (None, 0.01, 0.05, 0.10)
RATE_SCALE = (1.0, 0.5, 0.25, 0.125)
DEVICES = (2, 3, 4, 5)
COMPRESSION = (1.0, 2.0, 4.0)
ALL_SECTIONS = ("sharded", "pallas", "multichannel", "frontier")

# energy pricing for the multichannel section (defaults are 0.0 —
# energy is opt-in): ESP32-class active power, WiFi-class radio power
ACTIVE_POWER_W = 0.5
TX_POWER_W = 0.24
RX_POWER_W = 0.12


def build_grid(smoke: bool) -> ScenarioGrid:
    models = {"mobilenet_v2": mobilenet_cost_profile()}
    if not smoke:
        models["resnet50"] = resnet50_cost_profile()
    return ScenarioGrid(
        models=models,
        links=dict(PROTOCOLS),
        n_devices=DEVICES,
        loss_p=LOSS_P,
        rate_scale=RATE_SCALE,
        devices=(ESP32,),
    )


def timed_sweep(grid, backend, known):
    """Warm (compile once), then time one steady-state sweep of ``grid``
    on ``backend``. ``known`` caches ``backend -> (SweepResult, wall_s)``
    across report sections, so the jax reference (and a ``--backend
    jax``/``sharded``/``pallas`` main run) is never re-solved."""
    if backend not in known:
        sweep(grid, solver="batched_dp", backend=backend)  # warm
        t0 = time.perf_counter()
        res = sweep(grid, solver="batched_dp", backend=backend)
        known[backend] = (res, time.perf_counter() - t0)
    return known[backend]


def run_sharded(grid, known=None) -> dict:
    """The ``sharded`` section: the grid swept with the scenario axis
    partitioned over every local JAX device, verified node-identical
    (splits, feasibility, objective) to the single-device JAX path it
    shards."""
    from repro.core.shard import scenario_shards

    known = {} if known is None else known
    jax_ref, jax_wall = timed_sweep(grid, "jax", known)
    sharded, sharded_wall = timed_sweep(grid, "sharded", known)

    node_identical = all(
        a.splits == b.splits and a.feasible == b.feasible
        and a.objective_cost_s == b.objective_cost_s
        for a, b in zip(jax_ref.rows, sharded.rows))
    return {
        "n_shards": scenario_shards(),
        "wall_s": round(sharded_wall, 4),
        "solve_s": round(sharded.solve_time_s, 4),
        "jax_single_device_wall_s": round(jax_wall, 4),
        "jax_single_device_solve_s": round(jax_ref.solve_time_s, 4),
        "scenarios_per_sec": round(sharded.n_scenarios / sharded_wall, 1),
        "node_identical_to_jax": node_identical,
    }


def run_pallas(grid, known=None) -> dict:
    """The ``pallas`` section: the grid swept by the fused kernel
    (``C`` never materialized), verified against the single-device JAX
    path. Every node must either match exactly or be an exact-cost tie
    — each divergent node's two plans are repriced with the float64
    scalar cost model and must agree to ~1 ulp (both optimal)."""
    from repro.core import solvers as S
    from repro.core.pallas_dp import DEFAULT_BLOCK_S, pallas_interpret_default

    known = {} if known is None else known
    jax_ref, jax_wall = timed_sweep(grid, "jax", known)
    pallas, pallas_wall = timed_sweep(grid, "pallas", known)

    combine = "max" if grid.objective == "bottleneck" else "sum"

    def reprice(sc, splits):
        m = grid.cost_model(sc)
        return S.total_cost(m.cost_segment_fn(), splits,
                            m.profile.num_layers, combine)

    node_identical = True
    n_ties = 0
    ties_ok = True
    costs_ok = True
    for a, b in zip(jax_ref.rows, pallas.rows):
        ca, cb = a.objective_cost_s, b.objective_cost_s
        if math.isinf(ca) or math.isinf(cb):
            costs_ok = costs_ok and math.isinf(ca) and math.isinf(cb)
        else:
            costs_ok = costs_ok and abs(ca - cb) <= 1e-5 * abs(ca)
        if a.splits == b.splits and a.feasible == b.feasible:
            continue
        node_identical = False
        n_ties += 1
        if a.feasible != b.feasible:
            ties_ok = False
            continue
        ra, rb = reprice(a.scenario, a.splits), reprice(b.scenario, b.splits)
        if abs(ra - rb) > 1e-12 * max(abs(ra), 1e-300):
            ties_ok = False
    return {
        "interpret": pallas_interpret_default(),
        "block_s": DEFAULT_BLOCK_S,
        "wall_s": round(pallas_wall, 4),
        "solve_s": round(pallas.solve_time_s, 4),
        "build_s": round(pallas.build_time_s, 4),
        "jax_wall_s": round(jax_wall, 4),
        "scenarios_per_sec": round(pallas.n_scenarios / pallas_wall, 1),
        "node_identical_to_jax": node_identical,
        "n_tie_divergences": n_ties,
        "divergences_are_exact_ties": ties_ok,
        "costs_allclose_to_jax": costs_ok,
        "note": ("interpret mode times the Pallas interpreter, not a "
                 "compiled kernel: correctness only; the >=10x fusion "
                 "target applies on real accelerator hardware"
                 if pallas_interpret_default() else
                 "compiled pallas kernel (Mosaic)"),
    }


def build_multichannel_grid(smoke: bool) -> ScenarioGrid:
    """Contention × energy-budget grid for the multichannel section:
    powered links/devices, shared-channel groups, and Joule caps chosen
    from the energy tensor's own percentiles so the budget axis spans
    binding and slack regimes."""
    dev = replace(ESP32, active_power_w=ACTIVE_POWER_W)
    links = {name: replace(lk, tx_power_w=TX_POWER_W, rx_power_w=RX_POWER_W)
             for name, lk in PROTOCOLS.items()}
    ref = replace(paper_cost_model("mobilenet_v2", "esp_now"),
                  link=links["esp_now"], devices=(dev,))
    E = ref.energy_cost_tensor(max(DEVICES))
    fin = E[np.isfinite(E)]
    tight = float(np.percentile(fin, 55.0))
    loose = float(np.percentile(fin, 95.0))
    return ScenarioGrid(
        models={"mobilenet_v2": mobilenet_cost_profile()},
        links=links,
        n_devices=(2, 3) if smoke else DEVICES,
        loss_p=(None, 0.05) if smoke else LOSS_P,
        devices=(dev,),
        contention_groups=(1, 2, 4),
        energy_budgets=(None, loose, tight),
        mac_efficiency=0.9,
    )


def run_multichannel(smoke: bool = True) -> dict:
    """The ``multichannel`` section: the contention × budget grid swept
    batched vs the scalar budget-filtered ``optimal_dp`` loop, verified
    bit-identical; plus the degenerate single-channel bit-exactness and
    per-segment budget-respect audits the property suite pins."""
    grid = build_multichannel_grid(smoke)

    t0 = time.perf_counter()
    batched = sweep(grid, solver="batched_dp")
    batched_wall = time.perf_counter() - t0

    t0 = time.perf_counter()
    scalar = sweep_scalar(grid, solver="optimal_dp")
    scalar_wall = time.perf_counter() - t0

    mismatches = parity_report(batched, scalar)

    # degenerate single-channel path: bit-exact vs the plain solve
    ref = replace(paper_cost_model("mobilenet_v2", "esp_now"),
                  link=replace(PROTOCOLS["esp_now"], tx_power_w=TX_POWER_W,
                               rx_power_w=RX_POWER_W),
                  devices=(replace(ESP32, active_power_w=ACTIVE_POWER_W),))
    C = stack_cost_tensors([ref], 3, channels=COST_CHANNELS)
    deg = solve_multi_channel(C[:1], channels=("latency",))
    plain = solve_batched(C[0])
    degenerate_ok = (np.array_equal(deg.splits, plain.splits)
                     and np.array_equal(deg.cost_s, plain.cost_s))

    # every budgeted feasible plan keeps every segment within budget
    # (scalar energy oracle re-pricing — not the tensor that masked it)
    budget_ok = True
    n_budgeted = 0
    for row in batched.rows:
        sc = row.scenario
        if sc.energy_budget is None:
            continue
        n_budgeted += 1
        if not row.feasible:
            continue
        m = grid.cost_model(sc)
        efn = m.energy_segment_fn()
        L = m.profile.num_layers
        bounds = (0,) + tuple(row.splits) + (L,)
        for k in range(sc.n_devices):
            if efn(bounds[k] + 1, bounds[k + 1], k + 1) > sc.energy_budget:
                budget_ok = False

    return {
        "n_scenarios": grid.size,
        "n_feasible": sum(r.feasible for r in batched.rows),
        "n_budgeted": n_budgeted,
        "contention_groups": list(grid.contention_groups),
        "batched_wall_s": round(batched_wall, 4),
        "scalar_wall_s": round(scalar_wall, 4),
        "speedup_x": round(scalar_wall / batched_wall, 1),
        "scenarios_per_sec": round(grid.size / batched_wall, 1),
        "parity_ok": not mismatches,
        "parity_mismatches": mismatches[:10],
        "degenerate_bit_exact": degenerate_ok,
        "budget_respected": budget_ok,
    }


def build_frontier_grid(smoke: bool,
                        factors: tuple = COMPRESSION) -> ScenarioGrid:
    """Bottleneck-variant grid for the frontier section: the paper
    models × every protocol × the compression axis."""
    models = {"mobilenet_v2": mobilenet_cost_profile()}
    if not smoke:
        models["resnet50"] = resnet50_cost_profile()
    return ScenarioGrid(
        models=models,
        links=dict(PROTOCOLS),
        n_devices=(2, 3) if smoke else DEVICES,
        loss_p=(None, 0.05) if smoke else LOSS_P,
        devices=(ESP32,),
        compression_factors=factors,
    )


def run_frontier(smoke: bool = True) -> dict:
    """The ``frontier`` section: the compression-axis grid swept with
    the variant fold (ONE batched pass prices every (scenario, variant)
    pair) vs a per-variant loop of single-factor sweeps, verified
    bit-identical row-for-row AND against the scalar per-scenario
    oracle; plus the latency-vs-accuracy Pareto frontiers with a
    brute-force non-domination audit."""
    grid = build_frontier_grid(smoke)

    t0 = time.perf_counter()
    batched = sweep(grid, solver="batched_dp")
    batched_wall = time.perf_counter() - t0

    # the loop the fold replaces: one sweep per compression factor
    t0 = time.perf_counter()
    per_variant = [sweep(build_frontier_grid(smoke, (cf,)),
                         solver="batched_dp")
                   for cf in COMPRESSION]
    loop_wall = time.perf_counter() - t0

    by_key = {(r.scenario.describe(), r.scenario.compression): r
              for res in per_variant for r in res.rows}
    loop_identical = all(
        (row := by_key.get((r.scenario.describe(),
                            r.scenario.compression))) is not None
        and row.splits == r.splits and row.feasible == r.feasible
        and row.objective_cost_s == r.objective_cost_s
        for r in batched.rows)

    t0 = time.perf_counter()
    scalar = sweep_scalar(grid, solver="optimal_dp")
    scalar_wall = time.perf_counter() - t0
    mismatches = parity_report(batched, scalar)

    # Pareto frontiers + the O(n^2) non-domination audit
    fronts = batched.pareto()
    frontier_ok = True
    identity_on_every_frontier = True
    for key, front in fronts.items():
        rows = list(front.rows)
        group = [r for r in batched.rows
                 if (r.scenario.model, r.scenario.protocol,
                     r.scenario.n_devices) == key]
        feas = [r for r in group if r.feasible]
        for r in feas:
            dominated = any(
                o.total_latency_s <= r.total_latency_s
                and o.accuracy_proxy >= r.accuracy_proxy
                and (o.total_latency_s, o.accuracy_proxy)
                != (r.total_latency_s, r.accuracy_proxy)
                for o in feas)
            if dominated == (r in rows):
                frontier_ok = False
        # the best full-accuracy (identity) row is never dominated
        ident = [r for r in feas if r.scenario.compression == 1.0]
        if ident and min(ident, key=lambda r: r.total_latency_s) not in rows:
            identity_on_every_frontier = False

    sizes = sorted(f.n_points for f in fronts.values())
    return {
        "n_scenarios": grid.size,
        "n_feasible": sum(r.feasible for r in batched.rows),
        "compression_factors": list(COMPRESSION),
        "batched_wall_s": round(batched_wall, 4),
        "per_variant_loop_wall_s": round(loop_wall, 4),
        "scalar_wall_s": round(scalar_wall, 4),
        "fold_speedup_x": round(loop_wall / batched_wall, 2),
        "speedup_x": round(scalar_wall / batched_wall, 1),
        "parity_ok": not mismatches,
        "parity_mismatches": mismatches[:10],
        "loop_identical": loop_identical,
        "n_frontiers": len(fronts),
        "frontier_sizes": sizes,
        "max_frontier_points": sizes[-1] if sizes else 0,
        "frontier_matches_bruteforce": frontier_ok,
        "identity_on_every_frontier": identity_on_every_frontier,
    }


def run(smoke: bool = True, backend: str = "numpy",
        sections: tuple = ALL_SECTIONS) -> dict:
    grid = build_grid(smoke)

    known: dict = {}
    if backend == "numpy":
        t0 = time.perf_counter()
        batched = sweep(grid, solver="batched_dp", backend=backend)
        batched_wall = time.perf_counter() - t0
    else:
        batched, batched_wall = timed_sweep(grid, backend, known)

    t0 = time.perf_counter()
    scalar = sweep_scalar(grid, solver="optimal_dp")
    scalar_wall = time.perf_counter() - t0

    mismatches = parity_report(batched, scalar)
    feasible = sum(r.feasible for r in batched.rows)
    return {
        "benchmark": "sweep_grid",
        "mode": "smoke" if smoke else "full",
        "backend": backend,
        "n_scenarios": grid.size,
        "n_feasible": feasible,
        "grid": {
            "models": sorted(grid.models), "protocols": sorted(grid.links),
            "n_devices": list(grid.n_devices),
            "loss_p": [p if p is not None else "base" for p in grid.loss_p],
            "rate_scale": list(grid.rate_scale),
        },
        "batched_wall_s": round(batched_wall, 4),
        "batched_solve_s": round(batched.solve_time_s, 4),
        "batched_build_s": round(batched.build_time_s, 4),
        "scalar_wall_s": round(scalar_wall, 4),
        "speedup_x": round(scalar_wall / batched_wall, 1),
        "scenarios_per_sec_batched": round(grid.size / batched_wall, 1),
        "scenarios_per_sec_scalar": round(grid.size / scalar_wall, 1),
        "parity_ok": not mismatches,
        "parity_mismatches": mismatches[:10],
        **({"sharded": run_sharded(grid, known)}
           if "sharded" in sections else {}),
        **({"pallas": run_pallas(grid, known)}
           if "pallas" in sections else {}),
        **({"multichannel": run_multichannel(smoke)}
           if "multichannel" in sections else {}),
        **({"frontier": run_frontier(smoke)}
           if "frontier" in sections else {}),
        "best": {
            name: {
                "scenario": row.scenario.describe(),
                "splits": list(row.splits),
                "total_latency_s": round(row.total_latency_s, 4),
            }
            for name, row in (
                (m, sweep_best(batched, m)) for m in sorted(grid.models)
            )
            if row is not None
        },
    }


def sweep_best(result, model):
    try:
        return result.best(model=model)
    except LookupError:
        return None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grid (256 scenarios, one model)")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax", "sharded", "pallas"))
    ap.add_argument("--json", default="BENCH_sweep.json",
                    help="path for the machine-readable result (empty to skip)")
    ap.add_argument("--csv", default="",
                    help="optionally dump the full per-scenario sweep table")
    ap.add_argument("--sections", default=",".join(ALL_SECTIONS),
                    help="comma-separated extra sections to run "
                         f"(default: all of {','.join(ALL_SECTIONS)}); "
                         "e.g. --sections multichannel for the "
                         "contention+energy smoke only. NOTE: a "
                         "section-limited JSON is NOT a valid "
                         "check_bench --sweep candidate (required "
                         "sections are missing by construction).")
    args = ap.parse_args()
    enable_compile_cache()
    sections = tuple(s for s in args.sections.split(",") if s)
    unknown = set(sections) - set(ALL_SECTIONS)
    if unknown:
        ap.error(f"unknown sections {sorted(unknown)}; "
                 f"options: {','.join(ALL_SECTIONS)}")

    print("\n=== sweep_grid: batched fleet sweep vs scalar per-scenario loop ===")
    report = run(smoke=args.smoke, backend=args.backend, sections=sections)
    print(f"scenarios: {report['n_scenarios']} "
          f"({report['n_feasible']} feasible; mode={report['mode']}, "
          f"backend={report['backend']})")
    print(f"batched: {report['batched_wall_s']}s "
          f"(solve {report['batched_solve_s']}s + build {report['batched_build_s']}s) "
          f"-> {report['scenarios_per_sec_batched']} scenarios/s")
    print(f"scalar loop: {report['scalar_wall_s']}s "
          f"-> {report['scenarios_per_sec_scalar']} scenarios/s")
    print(f"speedup: {report['speedup_x']}x  "
          f"parity (bit-identical splits): {report['parity_ok']}")
    if "sharded" in report:
        sh = report["sharded"]
        print(f"sharded: {sh['n_shards']} shard(s), {sh['wall_s']}s "
              f"({sh['scenarios_per_sec']} scenarios/s; 1-device jax "
              f"{sh['jax_single_device_wall_s']}s) "
              f"node-identical to jax: {sh['node_identical_to_jax']}")
    if "pallas" in report:
        pa = report["pallas"]
        print(f"pallas: {pa['wall_s']}s ({pa['scenarios_per_sec']} scenarios/s"
              f"{'; interpret mode' if pa['interpret'] else ''}) "
              f"node-identical to jax: {pa['node_identical_to_jax']} "
              f"({pa['n_tie_divergences']} exact-cost tie divergence(s), "
              f"all verified zero-regret: {pa['divergences_are_exact_ties']})")
    if "multichannel" in report:
        mc = report["multichannel"]
        print(f"multichannel: {mc['n_scenarios']} scenarios "
              f"({mc['n_budgeted']} budgeted, contention groups "
              f"{mc['contention_groups']}), batched {mc['batched_wall_s']}s "
              f"vs scalar {mc['scalar_wall_s']}s -> {mc['speedup_x']}x; "
              f"parity: {mc['parity_ok']}, degenerate bit-exact: "
              f"{mc['degenerate_bit_exact']}, budget respected: "
              f"{mc['budget_respected']}")
    if "frontier" in report:
        fr = report["frontier"]
        print(f"frontier: {fr['n_scenarios']} scenarios over compression "
              f"{fr['compression_factors']}, folded {fr['batched_wall_s']}s "
              f"vs per-variant loop {fr['per_variant_loop_wall_s']}s "
              f"({fr['fold_speedup_x']}x) vs scalar {fr['scalar_wall_s']}s "
              f"({fr['speedup_x']}x); parity: {fr['parity_ok']}, "
              f"loop-identical: {fr['loop_identical']}; "
              f"{fr['n_frontiers']} frontiers (sizes {fr['frontier_sizes']}), "
              f"non-domination audit: {fr['frontier_matches_bruteforce']}")
    for name, best in report["best"].items():
        print(f"best[{name}]: {best['scenario']} splits={best['splits']} "
              f"latency {best['total_latency_s']}s")
    if not report["parity_ok"]:
        for m in report["parity_mismatches"]:
            print("  MISMATCH:", m)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json}")
    if args.csv:
        grid = build_grid(args.smoke)
        with open(args.csv, "w") as f:
            f.write(sweep(grid, backend=args.backend).to_csv())
        print(f"wrote {args.csv}")

    if args.backend == "numpy":
        # the f64 NumPy backend is bit-identical to the scalar oracle;
        # jax/sharded (f32 by default) may break exact-cost ties differently
        assert report["parity_ok"], "batched sweep diverged from the scalar oracle"
    elif not report["parity_ok"]:
        print(f"note: backend={args.backend} differs from the scalar oracle on "
              f"{len(report['parity_mismatches'])}+ scenarios (expected: float32 "
              f"tie-breaking; use --backend numpy for bit-exact parity)")
    if "sharded" in report:
        assert report["sharded"]["node_identical_to_jax"], \
            "sharded sweep diverged from the single-device JAX path"
    # pallas node-identity contract: every node matches jax exactly, or
    # is a verified exact-cost tie (both plans optimal, zero f64 regret)
    if "pallas" in report:
        assert report["pallas"]["divergences_are_exact_ties"], \
            "pallas sweep diverged from the JAX path beyond exact-cost ties"
        assert report["pallas"]["costs_allclose_to_jax"], \
            "pallas sweep costs drifted from the JAX path"
    if "multichannel" in report:
        mc = report["multichannel"]
        assert mc["parity_ok"], \
            "multichannel batched sweep diverged from the scalar budget oracle"
        assert mc["degenerate_bit_exact"], \
            "single-channel solve_multi_channel diverged from solve_batched"
        assert mc["budget_respected"], \
            "a budgeted plan holds an over-budget segment"
    if "frontier" in report:
        fr = report["frontier"]
        assert fr["parity_ok"], \
            "variant-folded sweep diverged from the scalar (split, variant) oracle"
        assert fr["loop_identical"], \
            "variant-folded sweep diverged from the per-variant loop"
        assert fr["frontier_matches_bruteforce"], \
            "pareto() diverged from the brute-force non-dominated filter"
        assert fr["identity_on_every_frontier"], \
            "a frontier dropped the best full-accuracy (identity) row"
    if not math.isfinite(report["speedup_x"]) or report["speedup_x"] < 10:
        print(f"WARNING: speedup {report['speedup_x']}x below the 10x target")


if __name__ == "__main__":
    main()
