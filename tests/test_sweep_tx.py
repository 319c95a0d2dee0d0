"""A sweep group's transmission vectors and profile-bank slots: priced as
arrays over the group (``sweep._group_tx_vectors``, ``sweep._group_bank``),
they equal bit for bit the per-scenario loops they replaced.

The references below are those loops as they stood: one
``grid.effective_link`` and ``grid.variant_for`` call per scenario, and
one ``bank_index`` call per device slot of every scenario. Each case
builds a grid, groups its scenarios by model as ``sweep`` does, and
compares all seven ``_group_tx_vectors`` outputs (their ``None``s
included) and ``bank``, ``bank_rows`` (in order) and ``bank_idx`` with
``np.array_equal``."""

from dataclasses import replace

import numpy as np
import pytest

from repro.core import sweep as SW
from repro.core.latency import SplitCostModel
from repro.core.profiles import (ESP32, PROTOCOLS, TPU_LINKS,
                                 mobilenet_cost_profile,
                                 resnet50_cost_profile)


def reference_tx_vectors(grid, profile, group):
    """The per-scenario loop: ``(TX, AIR, ENC, setup_s, feedback_s,
    tx_power_w, rx_power_w)``."""
    L = profile.num_layers
    act_raw = profile.segment_arrays.boundary_act_bytes[1:].astype(np.float64)
    variants = [grid.variant_for(sc) for sc in group]
    any_variant = any(v is not None for v in variants)
    packets_by_key = {}
    enc_by_factor = {}
    out = np.empty((len(group), L))
    air_out = np.empty((len(group), L)) if any_variant else None
    enc_out = np.zeros((len(group), L)) if any_variant else None
    setup_s = np.empty(len(group))
    feedback_s = np.empty(len(group))
    tx_power_w = np.empty(len(group))
    rx_power_w = np.empty(len(group))
    for i, (sc, v) in enumerate(zip(group, variants)):
        link = grid.effective_link(sc)
        setup_s[i] = link.t_setup_s
        feedback_s[i] = link.t_feedback_s
        tx_power_w[i] = link.tx_power_w
        rx_power_w[i] = link.rx_power_w
        factor = 1.0 if v is None else v.compression_factor
        K = packets_by_key.get((link.mtu_bytes, factor))
        if K is None:
            if v is None:
                act = act_raw
            else:
                act = np.where(act_raw > 0,
                               np.ceil(act_raw / v.compression_factor), 0.0)
            K = np.where(act > 0, np.ceil(act / link.mtu_bytes), 0.0)
            packets_by_key[(link.mtu_bytes, factor)] = K
        tx = K * link.packet_time_s()
        tx[-1] = 0.0
        if air_out is not None:
            air_out[i] = tx
        if v is not None:
            enc = enc_by_factor.get(factor)
            if enc is None:
                enc = np.where(act_raw > 0,
                               v.encoder_t_s + act_raw * v.encoder_s_per_byte,
                               0.0)
                enc[-1] = 0.0
                enc_by_factor[factor] = enc
            enc_out[i] = enc
            tx = tx + enc
        out[i] = tx
    return (out, air_out, enc_out, setup_s, feedback_s, tx_power_w,
            rx_power_w)


def reference_bank(grid, profile, group):
    """The ``bank_index`` loop: ``(bank, bank_rows, bank_idx)``."""
    n_max = max(sc.n_devices for sc in group)
    base_model = SplitCostModel(
        profile=profile, devices=grid.devices_for(group[0]),
        link=next(iter(grid.links.values())), objective=grid.objective)
    bank_rows = {}
    bank_mats = []

    def bank_index(dev, is_first):
        key = (dev, is_first)
        row = bank_rows.get(key)
        if row is None:
            row = len(bank_mats)
            bank_rows[key] = row
            bank_mats.append(base_model._local_cost_matrix(dev, is_first))
        return row

    bank_idx = np.zeros((len(group), n_max), dtype=np.int64)
    for gi, sc in enumerate(group):
        devs = grid.devices_for(sc)
        for k in range(1, sc.n_devices + 1):
            dev = devs[0] if len(devs) == 1 else devs[k - 1]
            bank_idx[gi, k - 1] = bank_index(dev, k == 1)
    return np.stack(bank_mats), bank_rows, bank_idx


def esp32_grid(**overrides):
    kw = dict(models={"r50": resnet50_cost_profile()}, links=dict(PROTOCOLS),
              n_devices=(2, 3, 4, 5), devices=(ESP32,))
    kw.update(overrides)
    return SW.ScenarioGrid(**kw)


def mixes_grid():
    slow = replace(ESP32, name="slow", compute_scale=1.7)
    small = replace(ESP32, name="small", mem_limit_bytes=4e6)
    links = {k: replace(v, tx_power_w=0.24, rx_power_w=0.12)
             for k, v in PROTOCOLS.items()}
    return esp32_grid(
        links=links, models={"r50": resnet50_cost_profile(),
                "mnv2": mobilenet_cost_profile()},
        loss_p=(None, 0.05), rate_scale=(1.0, 0.5),
        device_mixes={"slow_head": (slow, ESP32, ESP32, ESP32, ESP32),
                      "small_tail": (ESP32, ESP32, ESP32, ESP32, small),
                      "slow": (slow,)})


def dsv3_pipeline_grid():
    from repro.configs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.core.planner import pipeline_grid

    shapes = (ShapeSpec("prefill", "prefill", 2048, 1),
              ShapeSpec("decode", "decode", 4096, 8))
    return pipeline_grid(get_config("deepseek-v3"), shapes, (8, 16, 32),
                         range(2, 17), TPU_LINKS, (None, 1e-4, 5e-4),
                         (1.0, 0.5, 0.125))


CASES = {
    # the four ESP32 protocols at their own loss and rate
    "protocols": esp32_grid,
    "loss-and-rate": lambda: esp32_grid(loss_p=(None, 0.0, 0.05, 0.1),
                                        rate_scale=(0.25, 1.0, 1.5)),
    "contention": lambda: esp32_grid(loss_p=(None, 0.05),
                                     rate_scale=(1.0, 0.5),
                                     contention_groups=(1, 2, 4),
                                     mac_efficiency=0.9),
    "compression": lambda: esp32_grid(compression_factors=(1, 2, 4),
                                      loss_p=(None, 0.05),
                                      variant_encoder_t_s=2e-3,
                                      variant_encoder_s_per_byte=1e-7),
    "device-mixes": mixes_grid,
    "dsv3-pipeline": dsv3_pipeline_grid,
}


def groups_of(grid):
    groups = {}
    for sc in grid.scenarios():
        groups.setdefault(sc.model, []).append(sc)
    return groups


@pytest.mark.parametrize("case", sorted(CASES))
def test_build_bit_identical_to_scenario_loops(case):
    grid = CASES[case]()
    for model, group in groups_of(grid).items():
        profile = grid.models[model]
        want = reference_tx_vectors(grid, profile, group)
        got = SW._group_tx_vectors(grid, profile, group)
        assert len(got) == len(want) == 7
        for name, a, b in zip(("TX", "AIR", "ENC", "setup_s", "feedback_s",
                               "tx_power_w", "rx_power_w"), got, want):
            assert (a is None) == (b is None), (case, name)
            if b is not None:
                assert a.dtype == b.dtype and np.array_equal(a, b), \
                    (case, name)
        bank, rows, idx = reference_bank(grid, profile, group)
        got_bank, got_rows, got_idx = SW._group_bank(
            grid, profile, group, max(sc.n_devices for sc in group))
        assert np.array_equal(got_bank, bank)
        assert list(got_rows.items()) == list(rows.items())
        assert got_idx.dtype == idx.dtype and np.array_equal(got_idx, idx)
    # each case reaches the data it was built for
    if case == "compression":
        assert got[1] is not None and got[2].any()
    if case in ("device-mixes", "dsv3-pipeline"):
        assert len(got_rows) > 2
    if case == "contention":
        assert {sc.contention for sc in group} == {1, 2, 4}
