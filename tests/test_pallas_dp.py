"""Pallas backend suite (interpret mode on CPU — the CI ``pallas`` job).

Dense mode (``backend="pallas"`` through :func:`repro.core.sweep.
batched_optimal_dp`) reorders no arithmetic vs the JAX backend, so the
contract here is exact ``==`` on splits, costs and feasibility —
including non-tile-multiple scenario counts and layer counts straddling
the 128-lane boundary, where the +inf lane padding and replica rows
must stay invisible.

Fused mode (:func:`repro.core.pallas_dp.pallas_fused_optimal_dp`, the
``sweep()``/``build_surfaces()`` path) folds ``C = local + tx``
construction into the kernel; the <=1 ulp construction rounding may
break EXACT-cost ties toward a different equally-optimal plan, so
fused assertions are: feasibility ``==``, costs allclose, and any
divergent plan must reprice (float64) to the same optimum.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import pallas_dp as PD
from repro.core import solvers as S
from repro.core import sweep as SW
from repro.core.latency import (
    DeviceProfile,
    LayerCost,
    LinkProfile,
    ModelCostProfile,
    SplitCostModel,
)
from repro.core.planner import pipeline_grid
from repro.core.profiles import ESP32, PROTOCOLS, TPU_LINKS, resnet50_cost_profile
from repro.core.surface import build_surfaces

INF = float("inf")

# (S, N, L) corners: non-multiple-of-block_s S, L straddling the
# 128-lane tile (130), single scenario, single device, L == N
SHAPES = [(7, 4, 13), (1, 2, 5), (16, 3, 130), (5, 1, 9), (3, 6, 6)]


def make_C(Sn, N, L, seed, inf_frac=0.15):
    """Random dense cost tensor with invalid segments at +inf."""
    rng = np.random.RandomState(seed)
    C = rng.uniform(1e-3, 10.0, size=(Sn, N, L, L))
    C[rng.random(size=C.shape) < inf_frac] = INF
    il = np.tril_indices(L, -1)
    C[:, :, il[0], il[1]] = INF  # a > b is not a segment
    return C


def make_ns(Sn, N, seed):
    return np.random.RandomState(seed ^ 0x5EED).randint(1, N + 1, size=Sn)


def reprice(C_s, splits, L, combine):
    """Float64 scalar-oracle cost of one scenario's plan."""
    return S.total_cost(
        lambda a, b, k: float(C_s[k - 1, a - 1, b - 1]), splits, L, combine)


def assert_same_or_exact_tie(a, b, C, combine, ctx=""):
    """Fused-mode plan contract vs a dense result: identical nodes
    except exact-cost ties (zero float64-repriced regret)."""
    assert np.array_equal(a.feasible, b.feasible), ctx
    fin = a.feasible
    assert np.allclose(a.cost_s[fin], b.cost_s[fin], rtol=1e-5), ctx
    L = C.shape[-1]
    for s in np.flatnonzero(fin):
        if a.splits_tuple(s) == b.splits_tuple(s):
            continue
        ra = reprice(C[s], a.splits_tuple(s), L, combine)
        rb = reprice(C[s], b.splits_tuple(s), L, combine)
        assert abs(ra - rb) <= 1e-12 * max(abs(ra), 1e-300), \
            f"{ctx}: scenario {s} diverged with regret {rb - ra!r}"


# ---------------------------------------------------------------------------
# Dense mode: bitwise node-identity to backend="jax"
# ---------------------------------------------------------------------------


class TestDenseNodeIdentity:
    @pytest.mark.parametrize("combine", ["sum", "max"])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_bitwise_vs_jax(self, shape, combine):
        Sn, N, L = shape
        C = make_C(Sn, N, L, seed=hash(shape) & 0x7FFFFFFF)
        ns = make_ns(Sn, N, seed=Sn * 31 + N)
        for kw in ({}, {"n_devices": ns}):
            a = SW.batched_optimal_dp(C, combine=combine, backend="jax", **kw)
            b = SW.batched_optimal_dp(C, combine=combine, backend="pallas",
                                      **kw)
            assert b.backend == "pallas"
            assert np.array_equal(a.splits, b.splits), (shape, combine, kw)
            assert np.array_equal(a.cost_s, b.cost_s), (shape, combine, kw)
            assert np.array_equal(a.feasible, b.feasible), (shape, combine, kw)

    @pytest.mark.parametrize("combine", ["sum", "max"])
    def test_exact_ties_break_to_first_minimum(self, combine):
        """Small-integer costs are exact in float32 and tie everywhere:
        every device backend must break them to the oracle's FIRST
        minimum (on a TPU, Mosaic's and XLA's argmin once disagreed)."""
        rng = np.random.RandomState(5)
        C = rng.randint(1, 4, size=(9, 4, 12, 12)).astype(np.float64)
        il = np.tril_indices(12, -1)
        C[:, :, il[0], il[1]] = INF
        ref = SW.batched_optimal_dp(C, combine=combine)
        for backend in ("jax", "pallas"):
            got = SW.batched_optimal_dp(C, combine=combine, backend=backend)
            assert np.array_equal(ref.splits, got.splits), backend
            assert np.array_equal(ref.cost_s, got.cost_s), backend

    def test_all_k_bitwise_vs_jax(self):
        C = make_C(6, 4, 12, seed=7)
        ref = SW.batched_optimal_dp(C, return_all_k=True, backend="jax")
        got = SW.batched_optimal_dp(C, return_all_k=True, backend="pallas")
        assert sorted(got) == sorted(ref) == [1, 2, 3, 4]
        for n in ref:
            assert np.array_equal(ref[n].splits, got[n].splits), n
            assert np.array_equal(ref[n].cost_s, got[n].cost_s), n
            assert np.array_equal(ref[n].feasible, got[n].feasible), n

    def test_odd_block_s_exercises_replica_padding(self):
        """block_s=3 with S=7 pads to Sp=9: two replica rows that must
        never leak into the real scenarios' answers."""
        C = make_C(7, 3, 11, seed=11)
        a = SW.batched_optimal_dp(C, backend="jax")
        b = PD.pallas_optimal_dp(C, block_s=3)
        assert np.array_equal(a.splits, b.splits)
        assert np.array_equal(a.cost_s, b.cost_s)

    def test_explicit_interpret_true(self):
        C = make_C(4, 3, 9, seed=3)
        a = SW.batched_optimal_dp(C, backend="jax")
        b = PD.pallas_optimal_dp(C, interpret=True)
        assert np.array_equal(a.splits, b.splits)

    def test_empty_scenario_axis(self):
        C = make_C(0, 3, 8, seed=1)
        b = SW.batched_optimal_dp(C, backend="pallas")
        assert b.splits.shape == (0, 2)
        assert b.cost_s.shape == (0,)


# ---------------------------------------------------------------------------
# Fused mode: C never materialized; node-identical up to exact ties
# ---------------------------------------------------------------------------


def make_local_tx(Sn, N, L, seed):
    rng = np.random.RandomState(seed)
    local = rng.uniform(1e-3, 5.0, size=(N, L, L))
    il = np.tril_indices(L, -1)
    local[:, il[0], il[1]] = INF
    local[rng.random(size=local.shape) < 0.1] = INF
    tx = rng.uniform(0.0, 2.0, size=(Sn, L))
    return local, tx


class TestFusedKernel:
    @pytest.mark.parametrize("combine", ["sum", "max"])
    def test_matches_dense_on_materialized_C(self, combine):
        Sn, N, L = 9, 4, 14
        local, tx = make_local_tx(Sn, N, L, seed=21)
        C = local[None, :, :, :] + tx[:, None, None, :]
        a = SW.batched_optimal_dp(C, combine=combine, backend="jax")
        b = PD.pallas_fused_optimal_dp(local, None, tx, combine=combine)
        assert b.backend == "pallas"
        assert_same_or_exact_tie(a, b, C, combine, ctx=f"fused/{combine}")

    def test_frozen_rows_with_ns(self):
        Sn, N, L = 8, 4, 10
        local, tx = make_local_tx(Sn, N, L, seed=5)
        C = local[None] + tx[:, None, None, :]
        ns = make_ns(Sn, N, seed=5)
        a = SW.batched_optimal_dp(C, n_devices=ns, backend="jax")
        b = PD.pallas_fused_optimal_dp(local, None, tx, n_devices=ns)
        assert_same_or_exact_tie(a, b, C, "sum", ctx="fused/ns")
        assert np.array_equal(a.n_devices_s, b.n_devices_s)

    def test_all_k(self):
        Sn, N, L = 5, 4, 9
        local, tx = make_local_tx(Sn, N, L, seed=9)
        C = local[None] + tx[:, None, None, :]
        ref = SW.batched_optimal_dp(C, return_all_k=True, backend="jax")
        got = PD.pallas_fused_optimal_dp(local, None, tx, return_all_k=True)
        assert sorted(got) == sorted(ref)
        for n in ref:
            assert_same_or_exact_tie(ref[n], got[n], C, "sum",
                                     ctx=f"fused/all_k n={n}")

    def test_single_device_stack(self):
        local, tx = make_local_tx(6, 1, 7, seed=2)
        C = local[None] + tx[:, None, None, :]
        a = SW.batched_optimal_dp(C, backend="jax")
        b = PD.pallas_fused_optimal_dp(local, None, tx)
        assert np.array_equal(a.splits, b.splits)
        assert np.allclose(a.cost_s, b.cost_s, rtol=1e-6)

    def test_bank_idx_heterogeneous_mixes(self):
        """(bank, bank_idx) subgrouping: scenarios sharing a device
        stack share one fused launch; the scattered-back tables must
        match solving the gathered dense tensor."""
        Sn, N, L, B = 11, 3, 12, 4
        rng = np.random.RandomState(17)
        bank = rng.uniform(1e-3, 5.0, size=(B, L, L))
        il = np.tril_indices(L, -1)
        bank[:, il[0], il[1]] = INF
        tx = rng.uniform(0.0, 2.0, size=(Sn, L))
        bank_idx = rng.randint(0, B, size=(Sn, N))
        ns = make_ns(Sn, N, seed=17)
        C = bank[bank_idx] + tx[:, None, None, :]
        a = SW.batched_optimal_dp(C, n_devices=ns, backend="jax")
        b = PD.pallas_fused_optimal_dp(bank, bank_idx, tx, n_devices=ns)
        assert_same_or_exact_tie(a, b, C, "sum", ctx="bank_idx")

    @pytest.mark.parametrize("combine", ["sum", "max"])
    @pytest.mark.parametrize("n_mixes", [2, 3])
    def test_homogeneous_mixes_take_one_launch_each(self, monkeypatch,
                                                    n_mixes, combine):
        """1-tuple mixes over fleet sizes 2..N, dead slots at row 0 as
        the sweep leaves them: one launch a mix, and every table, split,
        cost and feasibility bit-identical to solving each row-0 stack
        alone."""
        N, L, per = 6, 9, 3
        rng = np.random.RandomState(40 + n_mixes)
        bank = rng.uniform(1e-3, 5.0, size=(2 * n_mixes, L, L))
        il = np.tril_indices(L, -1)
        bank[:, il[0], il[1]] = INF
        rows, ns = [], []
        for m in range(n_mixes):  # bank rows 2m (first), 2m + 1 (rest)
            for n in range(2, N + 1):
                row = [2 * m] + [2 * m + 1] * (n - 1) + [0] * (N - n)
                rows += [row] * per
                ns += [n] * per
        bank_idx, ns = np.array(rows), np.array(ns)
        tx = rng.uniform(0.0, 2.0, size=(len(ns), L))
        launches = _count_fused_launches(monkeypatch)
        got = PD.pallas_fused_optimal_dp(bank, bank_idx, tx, combine=combine,
                                         n_devices=ns)
        assert len(launches) == n_mixes
        _assert_same_as_row0_stacks(bank, bank_idx, tx, ns, combine, got)

    def test_heterogeneous_mix_fleet_sizes_share_one_launch(self,
                                                            monkeypatch):
        """A mix (d1, d2, d3) over fleet sizes 1-3: each shorter fleet's
        live slots are a prefix of the longest, so one launch serves
        all three (dead slots at row 0 would make three)."""
        N, L = 3, 10
        rng = np.random.RandomState(31)
        bank = rng.uniform(1e-3, 5.0, size=(3, L, L))
        il = np.tril_indices(L, -1)
        bank[:, il[0], il[1]] = INF
        bank_idx = np.array([[0, 0, 0], [0, 1, 0], [0, 1, 2]] * 4)
        ns = np.array([1, 2, 3] * 4)
        tx = rng.uniform(0.0, 2.0, size=(len(ns), L))
        launches = _count_fused_launches(monkeypatch)
        got = PD.pallas_fused_optimal_dp(bank, bank_idx, tx, n_devices=ns)
        assert len(launches) == 1
        _assert_same_as_row0_stacks(bank, bank_idx, tx, ns, "sum", got)

    @pytest.mark.parametrize("combine", ["sum", "max"])
    def test_dead_slots_are_never_read(self, combine):
        """NaN in every slot beyond the largest fleet size leaves every
        table bit-identical: the frozen-row contract that lets a
        scenario ride a stack that only extends its live slots."""
        Sn, N, L = 10, 6, 11
        local, tx = make_local_tx(Sn, N, L, seed=8)
        ns = np.random.RandomState(8).randint(1, N - 1, size=Sn)
        poisoned = local.copy()
        poisoned[int(ns.max()):] = np.nan
        ref = PD.pallas_fused_dp_tables(local, tx, combine, ns=ns)
        got = PD.pallas_fused_dp_tables(poisoned, tx, combine, ns=ns)
        for a, b in zip(ref[0], got[0]):
            assert np.array_equal(a, b)
        assert np.array_equal(ref[1], got[1])

    def test_all_k_and_ns_mutually_exclusive(self):
        local, tx = make_local_tx(3, 2, 5, seed=1)
        with pytest.raises(ValueError, match="mutually exclusive"):
            PD.pallas_fused_optimal_dp(local, None, tx, return_all_k=True,
                                       n_devices=[1, 2, 2])
        bank_idx = np.zeros((3, 2), dtype=int)
        with pytest.raises(ValueError, match="mutually exclusive"):
            PD.pallas_fused_optimal_dp(local, bank_idx, tx,
                                       return_all_k=True, n_devices=2)

    def test_shape_validation(self):
        local, tx = make_local_tx(3, 2, 5, seed=1)
        with pytest.raises(ValueError, match="local must be"):
            PD.pallas_fused_dp_tables(local[:, :, :3], tx)
        with pytest.raises(ValueError, match="tx must be"):
            PD.pallas_fused_dp_tables(local, tx[:, :3])
        with pytest.raises(ValueError, match="bank_idx must be"):
            PD.pallas_fused_optimal_dp(local, np.zeros((4, 2), dtype=int), tx)


def _count_fused_launches(monkeypatch):
    """Wrap ``PD._fused_launch``: the returned list gets each launch's
    kernel name."""
    launches = []
    launch = PD._fused_launch

    def counted(*args):
        launches.append(args[4])
        return launch(*args)

    monkeypatch.setattr(PD, "_fused_launch", counted)
    return launches


def _assert_same_as_row0_stacks(bank, bank_idx, tx, ns, combine, got):
    """Each stack with dead slots at bank row 0, solved alone on its own
    scenarios through the table path, gives the same raw tables as the
    joined stack its scenarios were launched on, and the same results as
    the walked launch, bit for bit."""
    N, L = bank_idx.shape[1], tx.shape[1]
    canon = np.where(np.arange(N)[None, :] >= ns[:, None], 0, bank_idx)
    stacks, inv = np.unique(canon, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    joined, launch_of, _ = PD._join_live_stacks(bank_idx, ns)
    for u, stack in enumerate(stacks):
        sel = np.flatnonzero(inv == u)
        dp_per_k, parents = PD.pallas_fused_dp_tables(
            bank[stack], tx[sel], combine, ns=ns[sel])
        ref = SW._results_from_dp_tables(dp_per_k, parents, L, N, len(sel),
                                         "pallas", ns[sel], False, 0.0)
        (launch,) = np.unique(launch_of[sel])
        jdp, jpar = PD.pallas_fused_dp_tables(bank[joined[launch]], tx[sel],
                                              combine, ns=ns[sel])
        for k in range(N):
            assert np.array_equal(jdp[k], dp_per_k[k]), u
        assert np.array_equal(jpar, parents), u
        assert np.array_equal(got.splits[sel], ref.splits), u
        assert np.array_equal(got.cost_s[sel], ref.cost_s), u
        assert np.array_equal(got.feasible[sel], ref.feasible), u
        assert np.array_equal(got.n_devices_s[sel], ref.n_devices_s), u


def _bank_path_on_tables(bank, bank_idx, tx, combine="sum", n_devices=None,
                         **opts):
    """:func:`PD.pallas_fused_optimal_dp`'s bank path with the split walk
    on the host: every joined stack's tables fetched through
    ``pallas_fused_dp_tables`` and one result selected from them."""
    Sn, L = tx.shape
    N = bank_idx.shape[1]
    ns = SW._normalize_ns(n_devices, Sn, N)
    stacks, launch_of, _ = PD._join_live_stacks(bank_idx, ns)
    dp_per_k = [np.empty((Sn, L)) for _ in range(N)]
    parents = np.empty((Sn, N - 1, L), dtype=np.int64)
    for u, stack in enumerate(stacks):
        sel = np.flatnonzero(launch_of == u)
        dpk, par = PD.pallas_fused_dp_tables(bank[stack], tx[sel], combine,
                                             ns=ns[sel], **opts)
        for k in range(N):
            dp_per_k[k][sel] = dpk[k]
        parents[sel] = par
    return SW._results_from_dp_tables(dp_per_k, parents, L, N, Sn, "pallas",
                                      ns, False, 0.0)


def _walk_bank(seed):
    """Three mixes over fleet sizes 1-5 on L = 11: one mix's later
    devices hold +inf wherever a segment ends past layer 3 (a memory
    cliff: its rows with more than one device are wholly infeasible)."""
    Sn, N, L = 41, 5, 11
    rng = np.random.RandomState(seed)
    bank = rng.uniform(1e-3, 5.0, size=(5, L, L))
    il = np.tril_indices(L, -1)
    bank[:, il[0], il[1]] = INF
    bank[4, :, 3:] = INF
    mixes = np.array([[0, 1, 1, 1, 1], [0, 2, 3, 2, 3], [0, 4, 4, 4, 4]])
    bank_idx = mixes[rng.randint(0, 3, size=Sn)]
    ns = rng.randint(1, N + 1, size=Sn)
    tx = rng.uniform(0.0, 2.0, size=(Sn, L))
    return bank, bank_idx, tx, ns


class TestWalkedBankPath:
    """The bank path without all-k selects each scenario's cost and walks
    its splits on the device; every field equals the table path's."""

    @pytest.mark.parametrize("combine", ["sum", "max"])
    def test_bit_identical_to_the_table_path(self, monkeypatch, combine):
        bank, bank_idx, tx, ns = _walk_bank(seed=61)
        launches = _count_fused_launches(monkeypatch)
        got = PD.pallas_fused_optimal_dp(bank, bank_idx, tx, combine=combine,
                                         n_devices=ns, block_s=3)
        assert launches == ["solve_fused_walk"] * 3
        ref = _bank_path_on_tables(bank, bank_idx, tx, combine, ns,
                                   block_s=3)
        # the cases the walk has to get right are all there: one-device
        # rows, launches with replica rows, wholly infeasible rows
        _, launch_of, _ = PD._join_live_stacks(bank_idx, ns)
        assert (ns == 1).any() and (np.bincount(launch_of) % 3).any()
        infeasible = ~ref.feasible
        assert infeasible.any() and ref.feasible.any()
        assert (ref.splits[infeasible] == -1).all()
        assert got.backend == "pallas" and got.n_devices == 5
        assert got.splits.dtype == np.int64 and got.cost_s.dtype == np.float64
        assert np.array_equal(got.splits, ref.splits)
        assert np.array_equal(got.cost_s, ref.cost_s)
        assert np.array_equal(got.feasible, ref.feasible)
        assert np.array_equal(got.n_devices_s, ref.n_devices_s)

    def test_without_fleet_sizes_every_row_walks_from_the_last_device(self):
        bank, bank_idx, tx, _ = _walk_bank(seed=62)
        got = PD.pallas_fused_optimal_dp(bank, bank_idx, tx, combine="max")
        ref = _bank_path_on_tables(bank, bank_idx, tx, "max")
        assert got.n_devices_s is None
        assert np.array_equal(got.splits, ref.splits)
        assert np.array_equal(got.cost_s, ref.cost_s)
        assert np.array_equal(got.feasible, ref.feasible)

    def test_all_k_still_fetches_the_tables(self, monkeypatch):
        bank, bank_idx, tx, _ = _walk_bank(seed=63)
        launches = _count_fused_launches(monkeypatch)
        got = PD.pallas_fused_optimal_dp(bank, bank_idx, tx,
                                         return_all_k=True)
        assert set(launches) == {"solve_fused"}
        assert sorted(got) == [1, 2, 3, 4, 5]


# ---------------------------------------------------------------------------
# Integration: sweep() and build_surfaces() fused paths
# ---------------------------------------------------------------------------


def tiny_grid():
    layers = tuple(
        LayerCost(f"l{i}", t_infer_s=0.01 * (i + 1), act_bytes=200 * (5 - i),
                  param_bytes=1_000, work_bytes=500)
        for i in range(5)
    )
    prof = ModelCostProfile("toy", layers, input_bytes=128)
    links = {
        "fast": LinkProfile("fast", 512, 1e6, t_setup_s=0.1,
                            t_feedback_s=0.01),
        "slow": LinkProfile("slow", 256, 1e5, t_ack_s=1e-3, t_setup_s=0.02),
    }
    return SW.ScenarioGrid(
        models={"toy": prof},
        links=links,
        n_devices=(2, 3),
        loss_p=(None, 0.1),
        rate_scale=(1.0, 0.5),
        devices=(DeviceProfile("d", t_tensor_alloc_s=1e-3),
                 DeviceProfile("e", compute_scale=1.4),
                 DeviceProfile("f", compute_scale=0.8)),
    )


class TestSweepBackend:
    def test_sweep_pallas_vs_jax(self):
        grid = tiny_grid()
        rj = SW.sweep(grid, backend="jax")
        rp = SW.sweep(grid, backend="pallas")
        assert rp.n_scenarios == rj.n_scenarios == grid.size
        for a, b in zip(rj.rows, rp.rows):
            assert a.feasible == b.feasible
            if not a.feasible:
                continue
            assert b.objective_cost_s == pytest.approx(
                a.objective_cost_s, rel=1e-5)
            if a.splits == b.splits:
                assert b.total_latency_s == pytest.approx(
                    a.total_latency_s, rel=1e-5)
                continue
            # divergent plan: must be an exact-cost tie under the f64 oracle
            m = grid.cost_model(a.scenario)
            fn = m.cost_segment_fn()
            L = m.profile.num_layers
            ra = S.total_cost(fn, a.splits, L)
            rb = S.total_cost(fn, b.splits, L)
            assert abs(ra - rb) <= 1e-12 * max(abs(ra), 1e-300)

    @pytest.mark.parametrize("grid_of", ["r50", "dsv3"])
    def test_sweep_rows_equal_the_table_path(self, monkeypatch, grid_of):
        """Every field of every row is the same whether the bank path
        walks the splits on the device or on the host; under x64 both
        equal ``backend="jax"``, whose dense operand is then built with
        the same float64 sum as the fused kernel's."""
        grid = _parity_grid(grid_of)
        with jax.enable_x64(True):
            x64 = [_fields(r) for r in SW.sweep(grid, backend="pallas").rows]
            assert x64 == [_fields(r) for r in
                           SW.sweep(grid, backend="jax").rows]
        walked = [_fields(r) for r in SW.sweep(grid, backend="pallas").rows]
        monkeypatch.setattr(PD, "pallas_fused_optimal_dp",
                            _bank_path_on_tables)
        assert walked == [_fields(r) for r in
                          SW.sweep(grid, backend="pallas").rows]
        feasible = [f[2] for f in walked]
        assert 0 < feasible.count(False) and 0 < feasible.count(True)

    def test_sweep_rejects_unknown_backend(self):
        grid = tiny_grid()
        with pytest.raises(ValueError, match="unknown backend"):
            SW.sweep(grid, backend="cuda")


def _fields(row):
    return (row.scenario, row.splits, row.feasible, row.objective_cost_s,
            row.total_latency_s, row.device_s, row.transmission_s,
            row.accuracy_proxy)


def _parity_grid(name):
    """ResNet50 on 2-5 ESP32s (sum), or a DeepSeek-shaped model of 14
    blocks (L = 16) as 2-16 pipeline stages (max), whose decode shape's
    latent cache makes few-stage plans infeasible."""
    if name == "r50":
        return SW.ScenarioGrid(models={"r50": resnet50_cost_profile()},
                               links=dict(PROTOCOLS), n_devices=(2, 3, 4, 5),
                               loss_p=(None, 0.05, 0.1), devices=(ESP32,))
    from test_deepseek_v3_pipeline import TINY, TINY_SHAPES

    cfg = dataclasses.replace(TINY, n_layers=14)
    return pipeline_grid(cfg, TINY_SHAPES, (1, 2), range(2, 17), TPU_LINKS,
                         loss_p=(None, 5e-4), rate_scale=(1.0, 0.25))


def switchy_cost_model():
    layers = (
        LayerCost("l1", t_infer_s=0.01, act_bytes=1500, param_bytes=100),
        LayerCost("l2", t_infer_s=0.01, act_bytes=100, param_bytes=100,
                  work_bytes=10_000),
        LayerCost("l3", t_infer_s=0.01, act_bytes=0, param_bytes=100,
                  work_bytes=10_000),
    )
    prof = ModelCostProfile("switchy", layers)
    dev = DeviceProfile("d", tensor_alloc_s_per_byte=1e-6)
    link = LinkProfile("lk", mtu_bytes=1000, rate_bytes_per_s=1e6)
    return SplitCostModel(profile=prof, devices=(dev,), link=link)


FAMILY_GRID = {"pt_scale": (1.0, 8.0, 64.0), "loss_p": (0.0, 0.2)}


class TestSurfacesBackend:
    def test_build_surfaces_pallas_vs_jax(self):
        m = switchy_cost_model()
        fam_j = build_surfaces(m, {"lk": m.link}, (1, 2, 3),
                               solver="batched_dp", backend="jax",
                               **FAMILY_GRID)
        fam_p = build_surfaces(m, {"lk": m.link}, (1, 2, 3),
                               solver="batched_dp", backend="pallas",
                               **FAMILY_GRID)
        assert sorted(fam_p) == sorted(fam_j) == [1, 2, 3]
        for n in fam_j:
            for name in fam_j[n].protocols:
                a = fam_j[n].protocols[name]
                b = fam_p[n].protocols[name]
                assert a.packet_time_s == b.packet_time_s
                assert a.loss_p == b.loss_p
                # node latencies are host-f64 prices of the chosen plans:
                # equal-cost tie divergence keeps them allclose
                assert np.allclose(a.latency_s, b.latency_s, rtol=1e-9,
                                   equal_nan=True), (n, name)
                if not np.array_equal(a.splits, b.splits):
                    ties = a.splits != b.splits
                    assert np.allclose(a.latency_s[ties.any(axis=-1)],
                                       b.latency_s[ties.any(axis=-1)],
                                       rtol=1e-12), (n, name)


# ---------------------------------------------------------------------------
# jit caching, options, and the backend registry
# ---------------------------------------------------------------------------


class TestJitCaching:
    def test_same_shape_repeat_does_not_retrace(self):
        C = make_C(6, 3, 9, seed=23)
        SW.batched_optimal_dp(C, backend="pallas")  # warm (traces at most once)
        before = PD._PALLAS_TRACE_COUNT
        SW.batched_optimal_dp(C, backend="pallas")
        SW.batched_optimal_dp(make_C(6, 3, 9, seed=24), backend="pallas")
        assert PD._PALLAS_TRACE_COUNT == before

    def test_same_shape_repeat_of_the_walk_does_not_retrace(self):
        bank, bank_idx, tx, ns = _walk_bank(seed=64)
        PD.pallas_fused_optimal_dp(bank, bank_idx, tx, n_devices=ns)  # warm
        before = PD._PALLAS_TRACE_COUNT
        PD.pallas_fused_optimal_dp(bank, bank_idx, tx, n_devices=ns)
        PD.pallas_fused_optimal_dp(bank, bank_idx, tx * 0.5, n_devices=ns)
        assert PD._PALLAS_TRACE_COUNT == before


class TestOptionsAndRegistry:
    def test_block_s_validated(self):
        C = make_C(2, 2, 5, seed=1)
        with pytest.raises(ValueError, match="block_s"):
            PD.pallas_optimal_dp(C, block_s=0)

    def test_interpret_default_is_on_off_tpu(self):
        import jax

        if jax.default_backend() == "tpu":
            pytest.skip("TPU host: interpret defaults off")
        assert PD.pallas_interpret_default() is True

    def test_registry_is_the_backend_set(self):
        assert set(SW.DP_BACKENDS) == {"numpy", "jax", "sharded", "pallas"}
        for fn in SW.DP_BACKENDS.values():
            assert callable(fn)

    def test_unknown_backend_error_names_every_backend(self):
        """Regression: the ValueError must enumerate the live registry,
        not a hardcoded subset that rots when a backend lands."""
        C = make_C(2, 2, 5, seed=1)
        with pytest.raises(ValueError) as ei:
            SW.batched_optimal_dp(C, backend="tpu")
        msg = str(ei.value)
        assert "'tpu'" in msg
        for name in SW.DP_BACKENDS:
            assert name in msg, f"error message omits backend {name!r}"
