"""``correct`` on the CPU at a size a test run holds: each driver runs a
tiny instance and comes out correct; the control (the reference one
precision down, in the program's place) and every fault a cell can have,
planted under the timed path, come out not correct."""

from __future__ import annotations

import dataclasses
import io

import ml_dtypes
import numpy as np
import pytest

import bench.run as R
from bench.spec import Benchmark
from bench.tests.conftest import shrink_gateway, shrink_sweep

BF16 = ml_dtypes.bfloat16
SEED = 2**31 + 12345  # more than 32 signed bits


def run_cell(cell, shrink, seconds="1.5", trace="0", root=R.ROOT):
    out = io.StringIO()
    return R.run(["--workload", cell, "--seed", str(SEED), "--seconds", seconds,
                  "--trace", trace], require_tpu=False, traffic_overrides=shrink,
                 root=root, out=out, err=io.StringIO())


# -- sound runs ------------------------------------------------------------------


def test_sweep_cell_runs_and_is_correct():
    res = run_cell("r50-whatif", shrink_sweep)
    assert res["correct"], res["checks"]
    assert res["metrics"]["scenarios_per_s"]["value"] > 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "checks"


def test_gateway_cell_runs_and_is_correct(pending_root):
    res = run_cell("mnv2-gateway", shrink_gateway, seconds="3", root=pending_root)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["observe_p99_us"]["value"] > 0 and m["adopt_p95_s"]["value"] > 0


# -- the control -------------------------------------------------------------------


def test_sweep_control_fails():
    from bench.control import sweep_answers
    from bench.drivers.sweep_loop import Traffic, compare

    cell = Benchmark().cell("r50-whatif")
    shrink_sweep(cell.traffic)
    traffic = Traffic(cell.config, cell.traffic, SEED)
    low = compare(traffic, [(0, sweep_answers(traffic, 0, BF16))], cell.limits)
    assert any(low[k] > cell.limits[k] for k in low), low
    exact = compare(traffic, [(0, sweep_answers(traffic, 0, np.float64))], cell.limits)
    assert all(exact[k] <= cell.limits[k] for k in exact), exact


def test_gateway_control_fails(pending_root):
    from bench.reference.costmodel import Deployment
    from bench.reference.surface import SurfaceReference

    cell = Benchmark(pending_root).cell("mnv2-gateway")
    dep = Deployment(cell.config)
    ref = SurfaceReference(dep)
    axes = ((0.25, 1.0, 4.0, 16.0, 20.8, 83.2), (0.0, 0.1), (2, 3, 5))
    low = ref.compare(SurfaceReference(dep, dtype=BF16).build(*axes), *axes)
    assert any(low[k] > cell.limits[k] for k in low), low
    exact = ref.compare(ref.build(*axes), *axes)
    assert all(exact[k] <= cell.limits[k] for k in exact), exact


# -- faults under the timed path -------------------------------------------------


def _sweep_fault(monkeypatch, wrap):
    import repro.core.sweep as SW

    real = SW.sweep
    monkeypatch.setattr(SW, "sweep", wrap(real))


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
            nxt = r.splits[1] if len(r.splits) > 1 else 52
            if r.feasible and r.splits and r.splits[0] + 1 < nxt:
                r = dataclasses.replace(r, splits=(r.splits[0] + 1,) + tuple(r.splits[1:]))
            rows.append(r)
        return dataclasses.replace(res, rows=tuple(rows))
    return sweep


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["state-unchanged", "half-the-batch", "answer-altered"])
def test_sweep_faults_are_not_correct(monkeypatch, fault):
    _sweep_fault(monkeypatch, fault)
    res = run_cell("r50-whatif", shrink_sweep)
    assert not res["correct"], res["checks"]


def _gateway_stale(monkeypatch):
    """A rebuild that hands back the surfaces the gateway started from
    (the base axes, whatever the request): its state unchanged."""
    from repro.core.async_replan import SurfaceRebuilder

    real = SurfaceRebuilder.build_sync

    def build_sync(self, req):
        return real(self, dataclasses.replace(req, pt_scale=self.pt_scale,
                                              loss_p=self.loss_p))

    monkeypatch.setattr(SurfaceRebuilder, "build_sync", build_sync)


def _gateway_half(monkeypatch):
    """Half of each rebuild's batch left out of the solve."""
    import repro.core.pallas_dp as PD

    real = PD.pallas_fused_optimal_dp

    def solve(bank, bank_idx, tx, **kw):
        out = real(bank, bank_idx, tx, **kw)
        half = tx.shape[0] // 2

        def cut(r):
            feas = r.feasible.copy()
            feas[half:] = False
            cost = r.cost_s.copy()
            cost[half:] = np.inf
            return dataclasses.replace(r, feasible=feas, cost_s=cost)
        return {n: cut(r) for n, r in out.items()} if isinstance(out, dict) else cut(out)

    monkeypatch.setattr(PD, "pallas_fused_optimal_dp", solve)


def _gateway_altered(monkeypatch):
    """Every decision a session adopts altered where it is made."""
    from repro.core.adaptive import AdaptiveSplitManager

    real = AdaptiveSplitManager._adopt

    def adopt(self, name, splits, chunk, lat, reason, variant=0):
        return real(self, name, splits, chunk - 1, lat, reason, variant=variant)

    monkeypatch.setattr(AdaptiveSplitManager, "_adopt", adopt)


@pytest.mark.parametrize("plant", [_gateway_stale, _gateway_half, _gateway_altered],
                         ids=["state-unchanged", "half-the-batch", "answer-altered"])
def test_gateway_faults_are_not_correct(monkeypatch, plant, pending_root):
    plant(monkeypatch)
    res = run_cell("mnv2-gateway", shrink_gateway, seconds="3", root=pending_root)
    assert not res["correct"], res["checks"]
