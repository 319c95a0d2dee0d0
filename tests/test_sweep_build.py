"""The cost operand of a sweep group that is not solved fused: built in
blocks of scenarios (``sweep._group_operand``), it equals bit for bit
the one-shot float64 formula it replaced.

The reference below is that formula as it stood: the whole group's
gather plus ``TX``, the whole group's energy tensor (its radio powers
read from ``grid.effective_link`` per scenario), and
``apply_energy_budget``. Each case spies on ``_group_operand`` inside a
real ``sweep``, rebuilds the reference from the same group data, and
compares every entry (dead device slots included) with
``np.array_equal``: in float64 on ``numpy``, and against the
reference's cast on the device backends, which is also the cast the
device transfer of the float64 tensor makes."""

import sys
from dataclasses import replace

import numpy as np
import pytest

from repro.core import sweep as SW
from repro.core.latency import LayerCost, ModelCostProfile, SplitCostModel
from repro.core.profiles import ESP32, PROTOCOLS, resnet50_cost_profile
from repro.core.sweep import INF

BOARD = replace(ESP32, active_power_w=0.5)
LINKS = {k: replace(v, tx_power_w=0.24, rx_power_w=0.12)
         for k, v in PROTOCOLS.items()}
# ResNet50 segments on these boards cost 3-74 J (5th to 95th percentile)
BUDGETS = (None, 70.0, 30.0)


def reference_operand(grid, group, bank, bank_rows, bank_idx, TX, AIR, ENC,
                      budgets):
    """(masked float64 C, float64 E): the one-shot formula."""
    C = bank[bank_idx]
    C += TX[:, None, None, :]
    AIR = TX if AIR is None else AIR
    L = AIR.shape[1]
    row_power = np.zeros(len(bank), dtype=np.float64)
    for (dev, _is_first), row in bank_rows.items():
        row_power[row] = dev.active_power_w
    with np.errstate(invalid="ignore"):
        e_bank = np.where(np.isfinite(bank),
                          row_power[:, None, None] * bank, INF)
    E = e_bank[bank_idx]
    if ENC is not None:
        pw = row_power[bank_idx]
        E = E + pw[:, :, None, None] * ENC[:, None, None, :]
    rx_t = np.zeros_like(AIR)
    rx_t[:, 1:] = AIR[:, : L - 1]
    tx_p = np.array([grid.effective_link(sc).tx_power_w for sc in group])
    rx_p = np.array([grid.effective_link(sc).rx_power_w for sc in group])
    E = E + (tx_p[:, None] * AIR)[:, None, None, :]
    E = E + (rx_p[:, None] * rx_t)[:, None, :, None]
    return SW.apply_energy_budget(C, E, budgets), E


def deep_model(N=4):
    """A model deep enough that one scenario's (N, L, L) float64 tensor
    outgrows a build block: every block holds one scenario."""
    L = int(np.sqrt(SW._BLOCK_BYTES / (N * 8))) + 1
    layers = tuple(LayerCost(f"l{i}", 0.02 * (1 + (i * 7) % 5),
                             act_bytes=900 * (1 + (i * 3) % 7),
                             param_bytes=1000, work_bytes=500)
                   for i in range(L))
    return ModelCostProfile("deep", layers, input_bytes=1024)


def on_an_entry():
    """One scenario whose budget equals one of its own energy entries,
    taken from the scalar model's tensor: ``E > budget`` is strict."""
    m = SplitCostModel(profile=resnet50_cost_profile(), devices=(BOARD,) * 5,
                       link=LINKS["esp_now"])
    E = m.energy_cost_tensor(5)
    fin = np.sort(E[np.isfinite(E)])
    return r50_grid(links={"esp_now": LINKS["esp_now"]}, n_devices=(5,),
                    loss_p=(None,),
                    energy_budgets=(float(fin[fin.size // 2]),))


def r50_grid(**overrides):
    kw = dict(models={"r50": resnet50_cost_profile()}, links=LINKS,
              n_devices=(2, 3, 4, 5), loss_p=(None, 0.05), devices=(BOARD,),
              energy_budgets=BUDGETS)
    kw.update(overrides)
    return SW.ScenarioGrid(**kw)


CASES = {
    "one-scenario": lambda: r50_grid(links={"esp_now": LINKS["esp_now"]},
                                     n_devices=(5,), loss_p=(None,),
                                     energy_budgets=(30.0,)),
    "fewer-scenarios-than-workers": lambda: r50_grid(
        models={"deep": deep_model()}, links={"ble": LINKS["ble"]},
        n_devices=(2, 3, 4), loss_p=(None,), energy_budgets=(3.0,)),
    "budget-on-an-entry": on_an_entry,
    "ragged-last-block": lambda: r50_grid(loss_p=(None, 0.02, 0.05),
                                          rate_scale=(1.0, 0.7)),
    "no-budgets": lambda: r50_grid(energy_budgets=(None,)),
    "mixed-budgets": r50_grid,
    "contention": lambda: r50_grid(contention_groups=(1, 2, 4),
                                   mac_efficiency=0.9),
    "compression": lambda: r50_grid(compression_factors=(1.0, 2.0),
                                    variant_encoder_t_s=2e-3,
                                    variant_encoder_s_per_byte=1e-7),
    "device-mixes": lambda: r50_grid(
        n_devices=(2, 4), devices=(),
        device_mixes={"hot_head": (replace(BOARD, active_power_w=0.9,
                                           compute_scale=0.7),
                                   BOARD, BOARD, BOARD),
                      "cool_tail": (BOARD, BOARD, BOARD,
                                    replace(BOARD, active_power_w=0.2))}),
}


def built(monkeypatch, grid, backend):
    """``sweep(grid, backend)``, spying on ``_group_operand``: a list of
    (group arguments, operand, counts), one per group."""
    seen = []
    build = SW._group_operand

    def spy(*args):
        out, counts = build(*args)
        seen.append((args[:-1], out, counts))
        return out, counts

    monkeypatch.setattr(SW, "_group_operand", spy)
    SW.sweep(grid, backend=backend)
    return seen


def block_rows(out):
    """Scenarios a build block holds, for an operand of ``out``'s shape."""
    _, N, L, _ = out.shape
    return max(1, SW._BLOCK_BYTES // (N * L * L * 8))


def groups_of(grid):
    groups = {}
    for sc in grid.scenarios():
        groups.setdefault(sc.model, []).append(sc)
    return list(groups.values())


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_operand_bit_identical_to_one_shot_formula(monkeypatch, case,
                                                   backend):
    grid = CASES[case]()
    seen = built(monkeypatch, grid, backend)
    assert len(seen) == len(groups_of(grid))
    for group, (args, out, counts) in zip(groups_of(grid), seen):
        bank, bank_rows, bank_idx, TX, AIR, ENC, tx_p, rx_p, budgets = args
        want, E = reference_operand(grid, group, bank, bank_rows, bank_idx,
                                    TX, AIR, ENC, budgets)
        assert out.dtype == SW._operand_dtype(backend)
        assert np.array_equal(out, want.astype(out.dtype))
        # the energy formula itself, the same bits as the one-shot one
        e_bank, row_power, tx_e, rx_e = SW._energy_terms(
            bank, bank_rows, TX if AIR is None else AIR, tx_p, rx_p)
        assert np.array_equal(SW._group_energy_tensor(
            e_bank, row_power, bank_idx, ENC, tx_e, rx_e,
            np.empty(E.shape)), E)
        over = int(np.count_nonzero(E > budgets[:, None, None, None]))
        blocks = -(-len(out) // block_rows(out))
        assert counts == {"blocks": blocks,
                          "workers": min(SW._CORES, blocks),
                          "budgeted": int(np.isfinite(budgets).sum()),
                          "masked": over}
    # each case reaches what it was built for
    (_, out, counts), *_ = seen
    if case == "no-budgets":
        assert counts["budgeted"] == counts["masked"] == 0
    else:
        assert counts["masked"] > 0
    if case == "fewer-scenarios-than-workers":
        assert counts["blocks"] == out.shape[0] == 3
    if case == "ragged-last-block":
        assert counts["blocks"] > 1 and out.shape[0] % block_rows(out) != 0
    if case == "budget-on-an-entry":
        assert (E == budgets[0]).any()
    if case == "compression":
        assert seen[0][0][5] is not None  # ENC


@pytest.mark.parametrize("backend", ["jax", "sharded", "pallas"])
def test_device_operand_is_the_transfer_cast(monkeypatch, backend):
    """The float32 operand is the bits the device held before: the
    device transfer of the float64 tensor rounds each entry alike."""
    import jax.numpy as jnp

    grid = r50_grid(n_devices=(3, 5))
    ((args, out, _),) = built(monkeypatch, grid, backend)
    want, _ = reference_operand(grid, grid.scenarios(), *args[:6], args[-1])
    assert np.array_equal(out, np.asarray(jnp.asarray(want)))


@pytest.mark.parametrize("cores", ["two", "four-per-core"])
def test_any_worker_count_builds_the_same_operand(monkeypatch, cores):
    """Two workers share the blocks; or a pool of four threads a core
    takes one-scenario blocks under a short switch interval."""
    grid = r50_grid(loss_p=(None, 0.02, 0.05, 0.08))  # 192 scenarios
    if cores == "two":
        workers = 2
    else:
        workers = min(4 * SW._CORES, 192)
        monkeypatch.setattr(SW, "_BLOCK_BYTES", 1)
        monkeypatch.setattr(SW, "_build_pool", None)
    monkeypatch.setattr(SW, "_CORES", workers)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ((args, out, counts),) = built(monkeypatch, grid, "jax")
    finally:
        sys.setswitchinterval(interval)
        if cores != "two":
            SW._build_pool[1].shutdown()
    want, E = reference_operand(grid, grid.scenarios(), *args[:6], args[-1])
    assert counts["blocks"] == -(-192 // block_rows(out)) >= workers
    assert counts["workers"] == workers
    assert counts["masked"] == np.count_nonzero(
        E > args[-1][:, None, None, None])
    assert np.array_equal(out, want.astype(np.float32))
