"""Benchmark orchestrator: one section per paper table/figure + the
beyond-paper planner benchmarks.

Usage:
  PYTHONPATH=src python benchmarks/run.py                 # every section
  PYTHONPATH=src python benchmarks/run.py sweep_grid ...  # named sections

Unknown section names fail with a one-line error listing the available
sections (no stack trace). Emits ``name,us_per_call,derived`` CSV lines
at the end (one per benchmark row) in addition to the human-readable
sections.

``SECTIONS`` is the single registry: every section registers its name
and runner ONCE there — the CLI vocabulary, the unknown-name error, and
the dispatch loop all derive from it (they used to be hand-listed in
two places, so a new section could be runnable but unknown to the
error message, or vice versa)."""

from __future__ import annotations

import argparse
import importlib
import sys
import time
from pathlib import Path

# make `from benchmarks import ...` work when launched as a script
# (`python benchmarks/run.py` puts benchmarks/ itself on sys.path, not
# the repo root that contains the package)
_ROOT = str(Path(__file__).resolve().parent.parent)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def _timed(name, derive):
    """Standard section runner: import lazily (so `run.py one_section`
    does not pay the startup cost of every other benchmark module),
    time ``run()``, print ``main()``'s human-readable table, emit one
    CSV row per benchmark row."""

    def runner(csv_lines):
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        rows = mod.run()
        us = (time.perf_counter() - t0) * 1e6 / max(1, len(rows))
        mod.main()
        for i, r in enumerate(rows):
            csv_lines.append(f"{name}[{i}],{us:.1f},{derive(r)}")

    return runner


def _run_sweep_grid(csv_lines):
    # fleet sweep: one summary row (scenarios/sec + scalar-vs-batched
    # speedup); us_per_call reflects the BATCHED engine only (run()
    # also times the ~100x-slower scalar baseline for the speedup)
    from benchmarks import sweep_grid

    sweep_report = sweep_grid.run(smoke=True)
    sweep_us = (sweep_report["batched_wall_s"] * 1e6
                / max(1, sweep_report["n_scenarios"]))
    csv_lines.append(
        f"sweep_grid[0],{sweep_us:.1f},"
        f"speedup={sweep_report['speedup_x']}x"
        f"_sps={sweep_report['scenarios_per_sec_batched']}"
        f"_parity={sweep_report['parity_ok']}")
    print(f"\n=== sweep_grid (smoke): {sweep_report['n_scenarios']} "
          f"scenarios, {sweep_report['speedup_x']}x over scalar loop, "
          f"parity={sweep_report['parity_ok']} ===")


def _run_surface_replan(csv_lines):
    # surface replanning: one summary row (observe() throughput of the
    # precomputed degradation surface vs the per-observe re-solve path)
    from benchmarks import surface_replan

    surf_report = surface_replan.run(smoke=True)
    a = surf_report["async"]
    csv_lines.append(
        f"surface_replan[0],{surf_report['observe_us_surface']},"
        f"speedup={surf_report['speedup_x']}x"
        f"_nodes={surf_report['n_nodes']}"
        f"_parity={surf_report['parity_ok']}"
        f"_async_inflight={a['inflight_over_steady_x']}x"
        f"_async_parity={a['parity_ok']}")
    print(f"=== surface_replan (smoke): {surf_report['n_nodes']} nodes, "
          f"{surf_report['speedup_x']}x observe() speedup, "
          f"parity={surf_report['parity_ok']}; async in-flight "
          f"{a['inflight_over_steady_x']}x steady-state, "
          f"async parity={a['parity_ok']} ===")


def _run_gateway(csv_lines):
    # fleet gateway: one summary row (observe handling p99 + storm
    # coalescing + the zero-stale-adoption / shared-rebuilder audits)
    from benchmarks import gateway_load

    gw_report = gateway_load.run(smoke=True)
    st, storm, audit = (gw_report["steady"], gw_report["storm"],
                        gw_report["audit"])
    gw_ok = (audit["zero_stale_adoptions"]
             and audit["single_shared_rebuilder"]
             and audit["percentile_parity_ok"])
    csv_lines.append(
        f"gateway[0],{st['observe_us_p50']},"
        f"p99us={st['observe_us_p99']}"
        f"_coalesce={storm['coalesce_x']}x"
        f"_swaps={storm['surface_swaps']}"
        f"_audit={gw_ok}")
    print(f"\n=== gateway (smoke): {gw_report['n_sessions']} sessions, "
          f"observe p99 {st['observe_us_p99']} us, storm "
          f"{storm['rebuild_requests']} requests -> "
          f"{storm['builds_started']} builds "
          f"({storm['coalesce_x']}x), audits={gw_ok} ===")


def _run_planner(csv_lines):
    # planner tier: one summary row (spec-resolved solve throughput +
    # serialization overhead + the spec/kwargs/process parity flags)
    from benchmarks import planner_scale

    rep = planner_scale.run(smoke=True)
    sv, ser = rep["solve"], rep["serialization"]
    ok = (ser["roundtrip_exact"] and rep["parity"]["spec_path_identical"]
          and rep["rebuild"]["pool_parity_ok"]
          and rep["rebuild"]["zero_stale_adoptions"])
    csv_lines.append(
        f"planner[0],{sv['us_per_scenario']},"
        f"sps={sv['scenarios_per_sec']}"
        f"_overhead={ser['overhead_pct_of_solve']}%"
        f"_ok={ok}")
    print(f"\n=== planner (smoke): {sv['n_scenarios']} scenarios through "
          f"PlannerService, {sv['scenarios_per_sec']} scenarios/s, spec "
          f"serialization {ser['overhead_pct_of_solve']}% of solve, "
          f"checks={ok} ===")


# THE registry: name -> runner(csv_lines). Insertion order is run order.
SECTIONS = {
    "table2_transmission": _timed(
        "table2_transmission",
        lambda r: f"{r['protocol']}/{r['split']}={r['model_ms']}ms"
                  f"/pk{r['model_packets']}"),
    "table3_processing": _timed(
        "table3_processing",
        lambda r: f"dev{r['device']}_infer={r['inference_ms']}ms"),
    "table4_rtt": _timed(
        "table4_rtt",
        lambda r: f"{r['protocol']}_rtt={r['rtt_s']}s_err{r['rtt_err_pct']}%"),
    "fig3_heuristics": _timed(
        "fig3_heuristics",
        lambda r: f"{r['model']}/{r['solver']}/N{r['devices']}="
                  f"{r['latency_s']}s"),
    "fig4_beam_vs_brute": _timed(
        "fig4_beam_vs_brute",
        lambda r: f"N{r['devices']}_beam={r['beam_s']}s_brute={r['brute_s']}s"),
    "planner_tpu": _timed(
        "planner_tpu",
        lambda r: f"{r['arch']}/{r['link']}_gain={r['gain_vs_uniform_pct']}%"),
    "sweep_grid": _run_sweep_grid,
    "surface_replan": _run_surface_replan,
    "gateway": _run_gateway,
    "planner": _run_planner,
}

BENCHMARKS = tuple(SECTIONS)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("names", nargs="*", metavar="benchmark",
                    help=f"benchmarks to run (default: all). "
                         f"Available: {', '.join(BENCHMARKS)}")
    args = ap.parse_args(argv)
    unknown = [n for n in args.names if n not in SECTIONS]
    if unknown:
        raise SystemExit(
            f"error: unknown benchmark name(s): {', '.join(unknown)}\n"
            f"available benchmarks: {', '.join(BENCHMARKS)}")
    selected = set(args.names) if args.names else set(BENCHMARKS)
    enable_compile_cache()

    csv_lines = ["name,us_per_call,derived"]
    for name, runner in SECTIONS.items():
        if name in selected:
            runner(csv_lines)

    print("\n=== CSV ===")
    for line in csv_lines:
        print(line)


if __name__ == "__main__":
    main()
