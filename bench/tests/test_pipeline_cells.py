"""The two cells of the pipeline what-ifs and the energy what-ifs, on the
CPU at a size a test run holds: each resolves its files, runs and comes
out correct; each cell's bfloat16 control and every fault planted under
the timed path come out not correct."""

from __future__ import annotations

import dataclasses
import io

import ml_dtypes
import numpy as np
import pytest

import bench.run as R
from bench.spec import Benchmark
from bench.tests.conftest import shrink_sweep

SEED = 2**31 + 54321  # more than 32 signed bits
CELLS = ("dsv3-pp-whatif", "r50-energy-whatif")


def shrink_pipeline(t: dict) -> None:
    """A pipeline grid small enough for the Pallas interpreter: two
    shapes, two chip-group sizes, four stage counts."""
    g = t["grid"]
    g["decode"]["count"] = 1
    g["chips_per_stage"] = [8, 32]
    g["stages"] = [2, 4, 12, 16]
    g["loss_p"]["draws"] = 1
    g["rate_scale"]["draws"] = 2
    t["check"] = {"rows_per_call": 48}


SHRINK = {"dsv3-pp-whatif": shrink_pipeline, "r50-energy-whatif": shrink_sweep}


def run_cell(cell, trace="0"):
    return R.run(["--workload", cell, "--seed", str(SEED), "--seconds", "1.5",
                  "--trace", trace], require_tpu=False,
                 traffic_overrides=SHRINK[cell], out=io.StringIO(),
                 err=io.StringIO())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    b = Benchmark()
    c = b.cell(cell)
    assert c.driver().Driver and c.limits
    e2e = {m["name"] for m in b.metrics_for(cell, "end_to_end")}
    assert e2e == {"scenarios_per_s", "setup_s"}
    assert c.readers("per_layer")


def test_pipeline_cell_grid_is_the_declared_size():
    from bench.drivers.pipeline_loop import Traffic

    c = Benchmark().cell("dsv3-pp-whatif")
    g = Traffic(c.config, c.traffic, SEED).grids[0]
    assert g.size == 4 * 3 * 15 * 2 * 4 * 16 == 23_040
    assert [s.kind for s in g.shapes] == ["prefill"] + ["decode"] * 3


def test_energy_cell_grid_is_the_declared_size():
    from bench.drivers.sweep_loop import Traffic

    c = Benchmark().cell("r50-energy-whatif")
    t = Traffic(c.config, c.traffic, SEED)
    assert t.grids[0].size == 4 * 4 * 8 * 8 * 3 * 3 == 9216
    assert t.budgets[0] is None and 0 < t.budgets[2] < t.budgets[1]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    res = run_cell(cell, trace="1")
    assert res["correct"], res["checks"]
    assert res["metrics"], res


def test_pipeline_host_shares_partition_the_window():
    # the grid build, the sweep's build, solve and rows are disjoint
    # intervals of the window: together they cannot pass 100%
    got = run_cell("dsv3-pp-whatif", trace="1")["metrics"]
    names = ("profile_pct.pipe", "build_pct.plan", "solve_pct.plan",
             "rows_pct.plan")
    shares = [got[n]["value"] for n in names]
    assert all(v > 0 for v in shares), got
    assert sum(shares) <= 100.0, got


def test_pipeline_control_fails():
    from bench.pipeline_control import answers, _traffic
    from bench.drivers.pipeline_loop import compare

    cell = Benchmark().cell("dsv3-pp-whatif")
    traffic = _traffic(cell, SEED)
    low = compare(traffic, [(0, answers(traffic, 0, ml_dtypes.bfloat16))],
                  cell.limits)
    assert any(low[k] > cell.limits[k] for k in low), low
    exact = compare(traffic, [(0, answers(traffic, 0, np.float64))], cell.limits)
    assert all(exact[k] <= cell.limits[k] for k in exact), exact


def test_energy_control_fails():
    from bench.drivers.sweep_loop import compare
    from bench.energy_control import _traffic, answers

    cell = Benchmark().cell("r50-energy-whatif")
    traffic = _traffic(cell, SEED)
    assert any(b is not None for b in traffic.grids[0].budgets)
    low = compare(traffic, [(0, answers(traffic, 0, ml_dtypes.bfloat16))],
                  cell.limits)
    assert any(low[k] > cell.limits[k] for k in low), low
    exact = compare(traffic, [(0, answers(traffic, 0, np.float64))], cell.limits)
    assert all(exact[k] <= cell.limits[k] for k in exact), exact


def _stale(real):
    first = []

    def sweep(grid, **kw):
        res = real(grid, **kw)
        if not first:
            first.append(res)
        return first[0]
    return sweep


def _half(real):
    def sweep(grid, **kw):
        res = real(grid, **kw)
        return dataclasses.replace(res, rows=res.rows[: len(res.rows) // 2])
    return sweep


def _altered(real):
    def sweep(grid, **kw):
        res = real(grid, **kw)
        rows = []
        for r in res.rows:
            if r.feasible and len(r.splits) > 1 and r.splits[0] + 1 < r.splits[1]:
                r = dataclasses.replace(r, splits=(r.splits[0] + 1,) + r.splits[1:])
            rows.append(r)
        return dataclasses.replace(res, rows=tuple(rows))
    return sweep


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["state-unchanged", "half-the-batch", "answer-altered"])
def test_pipeline_faults_are_not_correct(monkeypatch, fault):
    import repro.core.sweep as SW

    monkeypatch.setattr(SW, "sweep", fault(SW.sweep))
    res = run_cell("dsv3-pp-whatif")
    assert not res["correct"], res["checks"]
