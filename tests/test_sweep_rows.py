"""Sweep row assembly: every ``SweepRow`` field, and the row order, are
bit-identical to a per-row reference loop.

``sweep`` prices each group's rows in whole-array passes
(``sweep._group_rows``). The reference below is the per-scenario loop
that did the same job before: it walks one scenario at a time, asks the
grid for the scenario's effective link, sums its cuts and segments one
numpy scalar at a time, and places the rows by index. Each case feeds
the reference the very group tensors and solver result that ``sweep``
used, and every row must agree with ``==`` on every field (``inf``
included) and in type."""

from dataclasses import fields, replace

import numpy as np
import pytest

from repro.core import sweep as SW
from repro.core.latency import (
    DeviceProfile,
    LayerCost,
    LinkProfile,
    ModelCostProfile,
)
from repro.core.sweep import INF, SweepRow


def reference_rows(grid, idxs, group, res, bank, bank_idx, TX, L, rows):
    """The per-row loop, one scenario at a time, into ``rows`` by index."""
    for gi, (idx, sc) in enumerate(zip(idxs, group)):
        n = sc.n_devices
        splits_t = res.splits_tuple(gi)
        feasible = bool(res.feasible[gi])
        link = grid.effective_link(sc)
        if splits_t or n == 1:
            bounds = [0, *splits_t, L] if feasible else None
        else:
            bounds = None
        if feasible and bounds is not None:
            tx_total = float(np.sum(TX[gi, [b - 1 for b in bounds[1:-1]]])) \
                if len(bounds) > 2 else 0.0
            obj = float(res.cost_s[gi])
            seg_sum = float(sum(
                bank[bank_idx[gi, i], bounds[i], bounds[i + 1] - 1]
                + TX[gi, bounds[i + 1] - 1]
                for i in range(len(bounds) - 1)))
            device_s = seg_sum - tx_total
            total = obj + link.t_setup_s + link.t_feedback_s
            rows[idx] = SweepRow(
                scenario=sc, splits=splits_t, feasible=True,
                objective_cost_s=obj, total_latency_s=total,
                device_s=device_s, transmission_s=tx_total,
                accuracy_proxy=grid.accuracy_for(sc),
            )
        else:
            rows[idx] = SweepRow(
                scenario=sc, splits=splits_t, feasible=False,
                objective_cost_s=INF, total_latency_s=INF,
                device_s=INF, transmission_s=INF,
                accuracy_proxy=grid.accuracy_for(sc),
            )


# ---------------------------------------------------------------------------
# Grids, one per case
# ---------------------------------------------------------------------------


def toy_model(name="toy", L=8, scale=1.0):
    layers = tuple(
        LayerCost(f"l{i}", 0.01 * scale * (1 + (i * 7) % 5),
                  act_bytes=300 * (1 + (i * 3) % 7),
                  param_bytes=400 * (i + 1), work_bytes=200 * (1 + i % 3))
        for i in range(L)
    )
    return ModelCostProfile(name, layers, input_bytes=256)


LINKS = {
    "fast": LinkProfile("fast", 512, 2e5, loss_p=0.01, t_setup_s=0.1,
                        t_feedback_s=0.01, tx_power_w=0.3, rx_power_w=0.1),
    "slow": LinkProfile("slow", 256, 3e4, t_ack_s=1e-3, t_setup_s=0.02,
                        t_feedback_s=0.003, tx_power_w=0.2, rx_power_w=0.1),
}
DEVICE = DeviceProfile("d", t_tensor_alloc_s=1e-3, active_power_w=0.5)


def grid_of(**overrides):
    kw = dict(models={"toy": toy_model()}, links=LINKS, n_devices=(2, 3, 4),
              loss_p=(None, 0.05), rate_scale=(1.0, 0.4), devices=(DEVICE,))
    kw.update(overrides)
    return SW.ScenarioGrid(**kw)


def memory_bound_grid():
    # every layer's weights fit, but no fewer than 3 boards hold the model
    return grid_of(devices=(replace(DEVICE, mem_limit_bytes=8_000),),
                   n_devices=(1, 2, 3, 4))


def mixes_grid():
    # the tail board holds no layer: its 4-board fleets are infeasible,
    # and greedy, which probes the second board, keeps their splits
    slow = replace(DEVICE, name="slow", compute_scale=0.4)
    tiny = replace(DEVICE, name="tiny", mem_limit_bytes=3_000)
    return grid_of(devices=(DEVICE,),
                   device_mixes={"slow_head": (slow, DEVICE, DEVICE, DEVICE),
                                 "tiny_tail": (DEVICE, DEVICE, DEVICE, tiny)})


def budget_grid():
    return grid_of(energy_budgets=(None, 0.02, 0.006))


CASES = {
    "numpy-dp": (grid_of, "batched_dp", "numpy"),
    "numpy-beam": (grid_of, "batched_beam", "numpy"),
    "numpy-greedy": (grid_of, "batched_greedy", "numpy"),
    "jax-dp": (grid_of, "batched_dp", "jax"),
    "pallas-dp": (grid_of, "batched_dp", "pallas"),
    "fleet-of-one": (lambda: grid_of(n_devices=(1, 2, 5)), "batched_dp",
                     "numpy"),
    "memory-bound": (memory_bound_grid, "batched_dp", "numpy"),
    "device-mixes": (mixes_grid, "batched_dp", "numpy"),
    "device-mixes-greedy": (mixes_grid, "batched_greedy", "numpy"),
    "device-mixes-pallas": (mixes_grid, "batched_dp", "pallas"),
    "contention": (lambda: grid_of(contention_groups=(1, 2, 4),
                                   mac_efficiency=0.8), "batched_dp",
                   "numpy"),
    "energy-budgets": (budget_grid, "batched_dp", "numpy"),
    "energy-budgets-pallas": (budget_grid, "batched_dp", "pallas"),
    "energy-budgets-jax": (budget_grid, "batched_dp", "jax"),
    "compression": (lambda: grid_of(compression_factors=(1.0, 2.0, 4.0),
                                    variant_encoder_t_s=2e-3,
                                    variant_encoder_s_per_byte=1e-7),
                    "batched_dp", "numpy"),
    "two-models": (lambda: grid_of(models={"toy": toy_model(),
                                           "wide": toy_model("wide", 11, 1.7)},
                                   n_devices=(1, 3, 4)),
                   "batched_dp", "numpy"),
}


def sweep_with_reference(monkeypatch, grid, solver, backend):
    """``sweep(grid)`` and the reference loop's rows over the same group
    tensors and solver results, in grid order."""
    seen = []
    group_rows = SW._group_rows

    def spy(grid, group, res, bank, bank_idx, TX, setup_s, feedback_s):
        seen.append((group, res, bank, bank_idx, TX))
        return group_rows(grid, group, res, bank, bank_idx, TX, setup_s,
                          feedback_s)

    monkeypatch.setattr(SW, "_group_rows", spy)
    result = SW.sweep(grid, solver=solver, backend=backend, beam_width=3)
    order = grid.scenarios()
    groups = {}
    for idx, sc in enumerate(order):
        idxs, group = groups.setdefault(sc.model, ([], []))
        idxs.append(idx)
        group.append(sc)
    assert len(seen) == len(groups)
    rows = {}
    for (model, (idxs, group)), (g, res, bank, bank_idx, TX) in zip(
            groups.items(), seen):
        assert g == group
        reference_rows(grid, idxs, group, res, bank, bank_idx, TX,
                       grid.models[model].num_layers, rows)
    return result, tuple(rows[i] for i in range(len(order)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_bit_identical_to_reference_loop(monkeypatch, case):
    make, solver, backend = CASES[case]
    grid = make()
    result, want = sweep_with_reference(monkeypatch, grid, solver, backend)
    assert isinstance(result.rows, tuple)
    assert all(type(r) is SweepRow for r in result.rows)
    assert len(result.rows) == len(want) == grid.size
    for got, ref in zip(result.rows, want):
        for f in fields(SweepRow):
            a, b = getattr(got, f.name), getattr(ref, f.name)
            assert a == b and type(a) is type(b), (case, f.name, a, b)
    # each case reaches the rows it was built for
    feasible = [r.feasible for r in result.rows]
    assert any(feasible)
    if case.startswith(("memory-bound", "energy-budgets", "device-mixes")):
        assert not all(feasible)
    if case == "device-mixes-greedy":
        assert any(r.splits and not r.feasible for r in result.rows)
    if case in ("fleet-of-one", "two-models"):
        assert any(r.feasible and r.scenario.n_devices == 1
                   for r in result.rows)


@pytest.mark.parametrize("backend,budgets", [
    ("numpy", (None,)), ("pallas", (None,)),
    ("numpy", (None, 0.02, 0.006)), ("jax", (None, 0.02, 0.006))],
    ids=["numpy", "pallas", "numpy-budgets", "jax-budgets"])
def test_effective_link_once_per_scenario(monkeypatch, backend, budgets):
    grid = grid_of(contention_groups=(1, 2), compression_factors=(1.0, 2.0),
                   energy_budgets=budgets)
    calls = []
    effective_link = SW.ScenarioGrid.effective_link

    def counted(self, sc):
        calls.append(sc)
        return effective_link(self, sc)

    monkeypatch.setattr(SW.ScenarioGrid, "effective_link", counted)
    SW.sweep(grid, backend=backend)
    assert sorted(map(grid.scenarios().index, calls)) == list(range(grid.size))
