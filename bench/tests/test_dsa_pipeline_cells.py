"""The DSA pipeline cell (``glm52-pp-whatif``), on the CPU at a size a
test run holds: it resolves its files and comes out correct, the DSA
reference's layer table is the program's, and a fault planted in the
timed path comes out not correct: the indices not shipped with the
hidden state, or a decode step reading the whole cache."""

from __future__ import annotations

import io

import pytest

import bench.run as R
from bench.spec import Benchmark

SEED = 2**31 + 86420  # more than 32 signed bits
CELL = "glm52-pp-whatif"


def shrink_dsa(t: dict) -> None:
    """Two shapes (the prefill and one decode), two chip-group sizes,
    four stage counts."""
    g = t["grid"]
    g["decode"]["count"] = 1
    g["chips_per_stage"] = [8, 32]
    g["stages"] = [2, 4, 8, 16]
    g["loss_p"]["draws"] = 1
    g["rate_scale"]["draws"] = 2
    t["check"] = {"rows_per_call": 48}


def run_cell(trace="0"):
    return R.run(["--workload", CELL, "--seed", str(SEED), "--seconds", "1.5",
                  "--trace", trace], require_tpu=False,
                 traffic_overrides=shrink_dsa, out=io.StringIO(),
                 err=io.StringIO())


def test_cell_resolves_its_files():
    b = Benchmark()
    c = b.cell(CELL)
    assert c.driver().Driver and c.limits
    e2e = {m["name"] for m in b.metrics_for(CELL, "end_to_end")}
    assert e2e == {"scenarios_per_s", "setup_s"}
    assert set(c.readers("per_layer")) == {
        "build_pct.plan", "solve_pct.plan", "rows_pct.plan",
        "dp_kernel_ms.plan", "device_idle_pct.plan", "profile_pct.pipe",
        "dp_roofline_pct.pipe"}


def test_dsa_grid_is_the_declared_size():
    c = Benchmark().cell(CELL)
    driver = c.driver().Driver(c.config, c.traffic, SEED)
    g = driver.traffic.grids[0]
    assert g.size == 4 * 3 * 15 * 2 * 4 * 16 == 23_040
    assert [s.kind for s in g.shapes] == ["prefill"] + ["decode"] * 3
    assert type(driver.traffic.dep).__name__ == "DSADeployment"
    assert driver.records()["L"] == 80


@pytest.mark.parametrize("batch,seq,kv", [(2, 9000, None), (4, 1, 1500),
                                          (16, 1, 600_000)])
def test_reference_table_is_the_programs(batch, seq, kv):
    from bench.drivers.dsa_pipeline_loop import program_objects
    from bench.reference.dsa_pipeline import layer_table
    from repro.models.graph import arch_layer_graph

    c = Benchmark().cell(CELL)
    model, _ = program_objects(c.config)
    g = arch_layer_graph(model, batch, seq, kv_len=kv)
    t = layer_table(c.config, batch, seq, kv)
    assert [n.cut_index_elems for n in g.nodes] == [r["index_out"] for r in t]
    assert [n.cache_read_elems for n in g.nodes] == [r["cache_read"] for r in t]
    assert [n.flops for n in g.nodes] == pytest.approx(
        [r["flops"] for r in t], rel=1e-12)


def test_dsa_cell_runs_and_is_correct():
    res = run_cell(trace="1")
    assert res["correct"], res["checks"]
    assert {"profile_pct.pipe", "build_pct.plan", "solve_pct.plan",
            "rows_pct.plan"} <= set(res["metrics"])


def _unshipped(real):
    """The program's cost profile with no indices on any cut."""
    def profile(graph, **kw):
        import dataclasses

        nodes = tuple(dataclasses.replace(n, cut_index_elems=0)
                      for n in graph.nodes)
        return real(dataclasses.replace(graph, nodes=nodes), **kw)
    return profile


def _dense_reads(real):
    """The program's cost profile with every cache read whole."""
    def profile(graph, **kw):
        import dataclasses

        nodes = tuple(dataclasses.replace(n, cache_read_elems=n.cache_elems)
                      for n in graph.nodes)
        return real(dataclasses.replace(graph, nodes=nodes), **kw)
    return profile


@pytest.mark.parametrize("fault", [_unshipped, _dense_reads])
def test_a_planted_fault_is_caught(monkeypatch, fault):
    """Priced without the shipped indices, or with a decode step reading
    the whole cache, the program plans other cuts and costs than the
    reference."""
    from repro.core import planner

    monkeypatch.setattr(planner, "tpu_cost_profile",
                        fault(planner.tpu_cost_profile))
    res = run_cell()
    assert not res["correct"], res["checks"]
