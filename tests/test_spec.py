"""Planner-tier contract tests: PlanSpec serialization + spec≡kwargs parity.

Four families pin the contract:

* **Round-trip exactness** — ``PlanSpec.to_json``/``from_json`` is a
  field-exact bijection: finite floats bit-for-bit (``repr`` round-trip),
  non-finite floats through explicit tags (the payload itself stays
  strict, NaN-free JSON), tuples stay tuples, ``None`` loss entries stay
  ``None``, and every registered nested dataclass (cost model, variant
  bank) reconstructs ``==``-equal. Pickle round-trips too — the
  process-boundary contract.

* **Spec-path ≡ kwargs-path** — ``PlannerService`` resolves a spec by
  calling the public entry point it describes; these tests call BOTH
  paths and assert bitwise-identical results across all four
  ``DP_BACKENDS`` for the DP and both numpy-only solvers, plus
  multi-channel, variant-bank, cost-model-batch and surface-family
  solves.

* **One direction** — every public entry point runs with
  ``PlannerService`` replaced by a class that refuses every call: the
  engines never call up into the spec tier.

* **Process boundary** — a spec serialized to JSON, shipped to a
  subprocess (spawn, so the child proves importability from scratch)
  and solved there returns bitwise-identical results; a
  ``ProcessPoolExecutor``-backed ``SurfaceRebuilder`` adopts a rebuilt
  surface node-identical to the synchronous build, with zero stale
  adoptions, end-to-end through ``FleetGateway``.
"""

import math
import pickle
import time

import numpy as np
import pytest

from repro.core import planner as PL
from repro.core import sweep as SW
from repro.core.async_replan import cpu_process_pool
from repro.core.latency import COST_CHANNELS
from repro.core.profiles import (
    ESP_NOW,
    PROTOCOLS,
    esp32_variant_bank,
    paper_cost_model,
)
from repro.core import spec as SPEC
from repro.core.spec import (
    PlannerService,
    PlanSpec,
    ScenarioRef,
    SurfaceAxes,
    build_surfaces_from_spec,
    channels_spec,
    models_spec,
    solve_from_json,
    surfaces_spec,
    tensor_spec,
    variant_bank_spec,
)
from repro.runtime.gateway import FleetGateway

INF = float("inf")
GRID = {"pt_scale": (1.0, 4.0, 16.0), "loss_p": (0.0, 0.1)}
NBYTES = 5488


def rand_tensor(rng, S=5, N=3, L=6, inf_frac=0.1):
    """Random stacked cost tensor with the solver's invalid-entry
    convention (+inf outside 1 <= a <= b <= L) plus some infeasible
    valid entries."""
    C = rng.uniform(0.1, 9.0, size=(S, N, L, L))
    mask = rng.uniform(size=C.shape) < inf_frac
    C[mask] = INF
    a = np.arange(1, L + 1)
    invalid = a[:, None] > a[None, :]
    C[:, :, invalid] = INF
    return C


def assert_results_identical(a, b):
    assert a.solver == b.solver and a.backend == b.backend
    assert a.n_devices == b.n_devices
    assert np.array_equal(a.splits, b.splits)
    assert np.array_equal(a.cost_s, b.cost_s)
    assert np.array_equal(a.feasible, b.feasible)
    if a.n_devices_s is None:
        assert b.n_devices_s is None
    else:
        assert np.array_equal(a.n_devices_s, b.n_devices_s)
    if a.channel_cost_s is None:
        assert b.channel_cost_s is None
    else:
        assert a.channels == b.channels
        assert np.array_equal(a.channel_cost_s, b.channel_cost_s)
    if a.variant is None:
        assert b.variant is None
    else:
        assert np.array_equal(a.variant, b.variant)


def assert_surfaces_identical(a, b):
    assert sorted(a.protocols) == sorted(b.protocols)
    for name in a.protocols:
        pa, pb = a.protocols[name], b.protocols[name]
        assert pa.packet_time_s == pb.packet_time_s, name
        assert pa.loss_p == pb.loss_p, name
        assert np.array_equal(pa.splits, pb.splits), name
        assert np.array_equal(pa.chunk_bytes, pb.chunk_bytes), name
        assert np.array_equal(pa.latency_s, pb.latency_s), name
        assert np.array_equal(pa.runner_splits, pb.runner_splits), name
        assert np.array_equal(pa.runner_latency_s, pb.runner_latency_s), name


def rich_spec():
    """A spec exercising every field family: nested cost model, protocol
    pairs, variant bank, non-finite budget, awkward floats."""
    return surfaces_spec(
        paper_cost_model("mobilenet_v2", "esp_now"),
        PROTOCOLS, (2, 3, 5),
        pt_scale=(1.0, 0.1 + 0.2, 16.0),
        loss_p=(None, 0.0, 0.1),
        beam_width=6,
        chunk_candidates=(256, 1024),
        energy_budget=INF,
        variants=esp32_variant_bank(),
        accuracy_floor=0.9,
    )


class TestRoundTrip:
    def test_rich_spec_json_round_trip_field_exact(self):
        spec = rich_spec()
        again = PlanSpec.from_json(spec.to_json())
        assert again == spec  # dataclass eq: every field, nested, exact
        # and the payload is strict JSON despite the inf budget
        assert "Infinity" not in spec.to_json()
        assert "NaN" not in spec.to_json()

    def test_awkward_floats_survive_bitwise(self):
        spec = PlanSpec(energy_budget=(0.1 + 0.2, 1e-308, INF, -INF),
                        accuracy_floor=1.0 / 3.0)
        again = PlanSpec.from_json(spec.to_json())
        for got, want in zip(again.energy_budget, spec.energy_budget):
            assert got == want and type(got) is float
        assert again.accuracy_floor == spec.accuracy_floor

    def test_nan_round_trips_as_nan(self):
        spec = PlanSpec(accuracy_floor=float("nan"))
        again = PlanSpec.from_json(spec.to_json())
        assert math.isnan(again.accuracy_floor)

    def test_bare_json_constants_rejected(self):
        with pytest.raises(ValueError, match="non-strict JSON constant"):
            PlanSpec.from_json('{"__type__": "PlanSpec", '
                               '"accuracy_floor": Infinity}')

    def test_unknown_type_tag_rejected(self):
        with pytest.raises(ValueError, match="unknown PlanSpec type tag"):
            PlanSpec.from_json('{"__type__": "os_system"}')

    def test_payload_must_decode_to_planspec(self):
        with pytest.raises(ValueError, match="not PlanSpec"):
            PlanSpec.from_json('{"__type__": "ScenarioRef", '
                               '"kind": "tensor"}')

    def test_none_loss_entries_and_tuples_preserved(self):
        spec = rich_spec()
        again = PlanSpec.from_json(spec.to_json())
        assert again.surface.loss_p == (None, 0.0, 0.1)
        assert isinstance(again.surface.pt_scale, tuple)
        assert isinstance(again.protocols, tuple)
        assert isinstance(again.protocols[0], tuple)
        assert again.variants == esp32_variant_bank()

    def test_pickle_round_trip(self):
        spec = rich_spec()
        assert pickle.loads(pickle.dumps(spec)) == spec

    def test_scenario_validation(self):
        with pytest.raises(ValueError, match="unknown scenario kind"):
            ScenarioRef(kind="wat")

    def test_solver_options_order_insensitive(self):
        a = tensor_spec(np.zeros((1, 2, 3, 3)), beam_width=4, return_all_k=False)
        b = tensor_spec(np.zeros((1, 2, 3, 3)), return_all_k=False, beam_width=4)
        assert a == b
        assert a.options() == {"beam_width": 4, "return_all_k": False}


class TestSpecKwargsParity:
    """The kwargs path and the explicit spec path must agree bitwise —
    the service calls the entry point with the spec's fields; these
    tests keep it that way."""

    @pytest.mark.parametrize("backend", sorted(SW.DP_BACKENDS))
    @pytest.mark.parametrize("combine", ["sum", "max"])
    def test_batched_dp_parity_all_backends(self, backend, combine):
        rng = np.random.default_rng(7)
        C = rand_tensor(rng)
        n = (2, 3, 2, 3, 2)
        via_kwargs = SW.solve_batched(C, solver="batched_dp",
                                      combine=combine, backend=backend,
                                      n_devices=n)
        spec = tensor_spec(C, solver="batched_dp", combine=combine,
                           backend=backend, n_devices=n)
        via_spec = PlannerService().solve(spec, C)
        assert_results_identical(via_kwargs, via_spec)

    @pytest.mark.parametrize("solver", ["batched_beam", "batched_greedy"])
    def test_beam_and_greedy_parity(self, solver):
        rng = np.random.default_rng(11)
        C = rand_tensor(rng)
        kw = {"beam_width": 3} if solver == "batched_beam" else {}
        via_kwargs = SW.solve_batched(C, solver=solver, **kw)
        spec = tensor_spec(C, solver=solver, **kw)
        via_spec = PlannerService().solve(spec, C)
        assert_results_identical(via_kwargs, via_spec)

    def test_spec_survives_json_and_still_solves_identically(self):
        rng = np.random.default_rng(13)
        C = rand_tensor(rng)
        spec = tensor_spec(C, combine="max", n_devices=3)
        direct = PlannerService().solve(spec, C)
        rehydrated = PlannerService().solve(
            PlanSpec.from_json(spec.to_json()), C)
        assert_results_identical(direct, rehydrated)

    def test_multi_channel_parity(self):
        rng = np.random.default_rng(17)
        S, N, L = 4, 3, 5
        C = np.stack([rand_tensor(rng, S=S, N=N, L=L)
                      for _ in COST_CHANNELS])
        kwargs = dict(energy_budget=20.0, channel_weights=(1.0, 0.25))
        via_kwargs = SW.solve_multi_channel(C, **kwargs)
        spec = channels_spec(C, **kwargs)
        via_spec = PlannerService().solve_multi_channel(spec, C)
        assert_results_identical(via_kwargs, via_spec)

    def test_variant_bank_parity(self):
        rng = np.random.default_rng(19)
        V = 3
        C = np.stack([rand_tensor(rng) for _ in range(V)])
        kwargs = dict(accuracy_proxy=(1.0, 0.95, 0.85), accuracy_floor=0.9)
        via_kwargs = SW.solve_variant_bank(C, **kwargs)
        spec = variant_bank_spec(C, **kwargs)
        via_spec = PlannerService().solve_variant_bank(spec, C)
        assert_results_identical(via_kwargs, via_spec)

    def test_plan_split_batch_parity(self):
        models = [paper_cost_model("mobilenet_v2", p)
                  for p in ("esp_now", "ble")]
        via_kwargs = PL.plan_split_batch(models, (2, 3))
        spec = models_spec(models, n_devices=(2, 3))
        via_spec = PlannerService().plan(spec, models)
        for a, b in zip(via_kwargs, via_spec):
            assert a.splits == b.splits
            assert a.segments == b.segments
            assert a.total_latency_s == b.total_latency_s
            assert a.objective_cost_s == b.objective_cost_s
            assert (a.variant, a.accuracy_proxy) == (b.variant,
                                                     b.accuracy_proxy)

    def test_build_surfaces_parity(self):
        from repro.core.surface import build_surfaces

        model = paper_cost_model("mobilenet_v2", "esp_now")
        via_kwargs = build_surfaces(model, PROTOCOLS, (2, 3), **GRID)
        spec = surfaces_spec(model, PROTOCOLS, (2, 3), **GRID)
        via_spec = PlannerService().build_surfaces(spec)
        assert sorted(via_kwargs) == sorted(via_spec) == [2, 3]
        for n in via_kwargs:
            assert_surfaces_identical(via_kwargs[n], via_spec[n])
        # and the process-boundary worker is the same call again
        via_worker = build_surfaces_from_spec(spec.to_json())
        for n in via_kwargs:
            assert_surfaces_identical(via_kwargs[n], via_worker[n])

    def test_operand_validation(self):
        C = np.zeros((2, 2, 4, 4))
        spec = tensor_spec(C)
        with pytest.raises(ValueError, match="shape"):
            PlannerService().solve(spec, np.zeros((2, 2, 5, 5)))
        with pytest.raises(ValueError, match="kind"):
            PlannerService().solve_multi_channel(spec, C)
        with pytest.raises(ValueError, match="needs n_devices"):
            PlannerService().plan(
                models_spec([], n_devices=None), [])


class _RefusingService:
    """Stands in for ``PlannerService``: every method call fails."""

    def _refuse(self, *args, **kwargs):
        raise AssertionError("an entry point called into PlannerService")

    solve = solve_multi_channel = solve_variant_bank = _refuse
    plan = build_surfaces = _refuse


def _batched_case():
    C = rand_tensor(np.random.default_rng(37))
    n = (2, 3, 2, 3, 2)
    return (lambda: SW.solve_batched(C, backend="numpy", n_devices=n),
            lambda: PlannerService().solve(
                tensor_spec(C, backend="numpy", n_devices=n), C),
            assert_results_identical)


def _multi_channel_case():
    rng = np.random.default_rng(41)
    C = np.stack([rand_tensor(rng, S=4, N=3, L=5) for _ in COST_CHANNELS])
    kw = dict(backend="numpy", energy_budget=20.0)
    return (lambda: SW.solve_multi_channel(C, **kw),
            lambda: PlannerService().solve_multi_channel(
                channels_spec(C, **kw), C),
            assert_results_identical)


def _variant_bank_case():
    rng = np.random.default_rng(43)
    C = np.stack([rand_tensor(rng) for _ in range(2)])
    kw = dict(backend="numpy", accuracy_proxy=(1.0, 0.95))
    return (lambda: SW.solve_variant_bank(C, **kw),
            lambda: PlannerService().solve_variant_bank(
                variant_bank_spec(C, **kw), C),
            assert_results_identical)


def _plans_identical(a, b):
    assert [(p.splits, p.total_latency_s) for p in a] == \
        [(p.splits, p.total_latency_s) for p in b]


def _plan_batch_case():
    models = [paper_cost_model("mobilenet_v2", p) for p in ("esp_now", "ble")]
    return (lambda: PL.plan_split_batch(models, (2, 3), backend="numpy"),
            lambda: PlannerService().plan(
                models_spec(models, n_devices=(2, 3), backend="numpy"),
                models),
            _plans_identical)


def _families_identical(a, b):
    assert sorted(a) == sorted(b)
    for n in a:
        assert_surfaces_identical(a[n], b[n])


def _surfaces_case():
    from repro.core.surface import build_surfaces

    model = paper_cost_model("mobilenet_v2", "esp_now")
    protocols = {"esp_now": ESP_NOW}
    return (lambda: build_surfaces(model, protocols, (2,), backend="numpy",
                                   **GRID),
            lambda: PlannerService().build_surfaces(
                surfaces_spec(model, protocols, (2,), backend="numpy",
                              **GRID)),
            _families_identical)


@pytest.mark.parametrize("case", [
    _batched_case, _multi_channel_case, _variant_bank_case,
    _plan_batch_case, _surfaces_case,
], ids=["solve_batched", "solve_multi_channel", "solve_variant_bank",
        "plan_split_batch", "build_surfaces"])
def test_entry_point_never_calls_the_spec_tier(case, monkeypatch):
    """Each public entry point runs its own body: with the spec tier's
    ``PlannerService`` refusing every call it still returns its normal
    result — the same one the spec path gives once the service is back."""
    via_kwargs, via_spec, same = case()
    monkeypatch.setattr(SPEC, "PlannerService", _RefusingService)
    got = via_kwargs()
    monkeypatch.undo()
    same(got, via_spec())


class TestManagersRouteThroughSpec:
    def test_adaptive_surface_spec_reproduces_auto_surface(self):
        from repro.core.adaptive import AdaptiveSplitManager

        mgr = AdaptiveSplitManager(
            cost_model=paper_cost_model("mobilenet_v2", "esp_now"),
            protocols=dict(PROTOCOLS), n_devices=2,
            solver="optimal_dp", surface_grid=GRID)
        spec = mgr.surface_spec()
        assert spec.scenario.kind == "surface"
        rebuilt = PlannerService().build_surfaces(spec)[2]
        assert_surfaces_identical(mgr.surface, rebuilt)

    def test_gateway_plan_spec_reproduces_family(self):
        gw = FleetGateway(paper_cost_model("mobilenet_v2", "esp_now"),
                          PROTOCOLS, (2, 3), surface_grid=GRID)
        # the gateway's own family came FROM this spec; a JSON round
        # trip of it rebuilds the identical family
        again = build_surfaces_from_spec(gw.plan_spec.to_json())
        assert sorted(again) == sorted(gw.surfaces)
        for n in gw.surfaces:
            assert_surfaces_identical(gw.surfaces[n], again[n])


def _spawn_pool(workers=1):
    return cpu_process_pool(workers)


class TestProcessBoundary:
    def test_subprocess_solve_bitwise_identical(self):
        rng = np.random.default_rng(31)
        C = rand_tensor(rng)
        spec = tensor_spec(C, combine="max", n_devices=(2, 3, 2, 3, 2))
        local = PlannerService().solve(spec, C)
        with _spawn_pool() as pool:
            remote = pool.submit(solve_from_json, spec.to_json(), C).result()
        assert_results_identical(local, remote)

    def test_process_pool_rebuild_through_gateway(self):
        """End-to-end: a gateway whose rebuilder runs on a process pool
        adopts a rebuilt surface node-identical to the synchronous
        build, with zero stale adoptions."""
        pool = _spawn_pool()
        gw = FleetGateway(paper_cost_model("mobilenet_v2", "esp_now"),
                          PROTOCOLS, (2, 3), surface_grid=GRID,
                          executor=pool)
        try:
            pt = 24.0 * ESP_NOW.transmission_latency_s(NBYTES)
            states = {name: (pt, 0.05) for name in PROTOCOLS}
            assert gw.rebuilder.request(2, states) == "queued"
            handle = gw.fanout.view()
            got = None
            deadline = time.monotonic() + 120.0
            while got is None and time.monotonic() < deadline:
                got = handle.poll(2)  # first poll launches on the pool
                if got is None:
                    time.sleep(0.05)
            assert got is not None, "process-pool rebuild never adopted"
            req = gw.rebuilder.last_request
            assert_surfaces_identical(got, gw.rebuilder.build_sync(req)[2])
            assert gw.rebuilder.builds_completed == 1
            # zero stale adoptions: generations strictly increase
            gens = [g for (n, g) in handle.adoptions if n == 2]
            assert gens == sorted(set(gens))
        finally:
            gw.rebuilder.shutdown()
            pool.shutdown(wait=True)
