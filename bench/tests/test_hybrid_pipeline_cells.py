"""The hybrid pipeline cell (``gc35-pp-whatif``) and the four-chip energy
cell (``r50-energy-4chip``), on the CPU at a size a test run holds: each
resolves its files and comes out correct; the hybrid reference's layer
table is the program's, and a fault planted in the hybrid's timed path
comes out not correct."""

from __future__ import annotations

import io

import pytest

import bench.run as R
from bench.spec import Benchmark
from bench.tests.conftest import shrink_sweep

SEED = 2**31 + 97531  # more than 32 signed bits


def shrink_hybrid(t: dict) -> None:
    """Two shapes (the prefill and one decode), two chip-group sizes,
    four stage counts."""
    g = t["grid"]
    g["decode"]["count"] = 1
    g["chips_per_stage"] = [8, 32]
    g["stages"] = [2, 4, 8, 16]
    g["loss_p"]["draws"] = 1
    g["rate_scale"]["draws"] = 2
    t["check"] = {"rows_per_call": 48}


def run_cell(cell, shrink, trace="0"):
    return R.run(["--workload", cell, "--seed", str(SEED), "--seconds", "1.5",
                  "--trace", trace], require_tpu=False,
                 traffic_overrides=shrink, out=io.StringIO(), err=io.StringIO())


@pytest.mark.parametrize("cell", ["gc35-pp-whatif", "r50-energy-4chip"])
def test_cell_resolves_its_files(cell):
    b = Benchmark()
    c = b.cell(cell)
    assert c.driver().Driver and c.limits
    e2e = {m["name"] for m in b.metrics_for(cell, "end_to_end")}
    assert e2e == {"scenarios_per_s", "setup_s"}
    assert c.readers("per_layer")


def test_hybrid_grid_is_the_declared_size():
    c = Benchmark().cell("gc35-pp-whatif")
    driver = c.driver().Driver(c.config, c.traffic, SEED)
    g = driver.traffic.grids[0]
    assert g.size == 4 * 3 * 15 * 2 * 4 * 16 == 23_040
    assert [s.kind for s in g.shapes] == ["prefill"] + ["decode"] * 3
    assert type(driver.traffic.dep).__name__ == "HybridDeployment"


def test_reference_table_is_the_programs():
    from bench.drivers.hybrid_pipeline_loop import program_objects
    from bench.reference.hybrid_pipeline import layer_table
    from repro.models.graph import arch_layer_graph

    c = Benchmark().cell("gc35-pp-whatif")
    model, _ = program_objects(c.config)
    for batch, seq, kv in ((2, 5000, None), (16, 1, 200_000)):
        g = arch_layer_graph(model, batch, seq, kv_len=kv)
        t = layer_table(c.config, batch, seq, kv)
        assert [n.state_elems for n in g.nodes] == [r["state"] for r in t]
        assert [n.flops for n in g.nodes] == pytest.approx(
            [r["flops"] for r in t], rel=1e-12)


def test_hybrid_cell_runs_and_is_correct():
    res = run_cell("gc35-pp-whatif", shrink_hybrid, trace="1")
    assert res["correct"], res["checks"]
    assert {"profile_pct.pipe", "build_pct.plan", "solve_pct.plan",
            "rows_pct.plan"} <= set(res["metrics"])


def test_energy_4chip_cell_runs_and_is_correct():
    # one CPU device: the sharded path on a one-device mesh
    res = run_cell("r50-energy-4chip", shrink_sweep)
    assert res["correct"], res["checks"]


def test_a_planted_state_fault_is_caught(monkeypatch):
    """The program priced without the recurrent state (state bytes 0)
    plans other cuts and costs than the reference."""
    from repro.core import planner

    real = planner.tpu_cost_profile
    monkeypatch.setattr(planner, "tpu_cost_profile",
                        lambda g, **kw: real(g, **dict(kw, state_dtype_bytes=0)))
    res = run_cell("gc35-pp-whatif", shrink_hybrid)
    assert not res["correct"], res["checks"]
