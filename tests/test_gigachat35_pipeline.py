"""GigaChat3.5-432B-A28B (10 MLA + 30 Gated DeltaNet layers) as pipeline
stages on v5e chip groups, against the plain float64 reference in
``bench/reference/hybrid_pipeline.py``.

The parameter count, the layer table at the published widths, the two
kinds of held memory (the fixed recurrent state, the latent cache that
grows with the KV length), the memory cliff, and the planner's normal
path: ``sweep(pipeline_grid(...))`` with the fused Pallas kernel
(interpret mode) equal to the numpy oracle in every row field, and the
oracle's plans equal to the reference bottleneck DP's."""

from __future__ import annotations

import hashlib
import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.drivers.hybrid_pipeline_loop import program_objects
from bench.reference.hybrid_pipeline import HybridDeployment, layer_table
from bench.reference.pipeline import bottleneck_tables, price
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.core.planner import pipeline_grid, tpu_cost_profile
from repro.core.profiles import TPU_LINKS
from repro.core.sweep import sweep
from repro.models.graph import arch_layer_graph, experts_touched

ARCH = "gigachat3.5-432b-a28b"
DEPLOYMENT = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "gigachat35-v5e-pp.json").read_text())
FULL = (3, 7, 11, 15, 19, 23, 27, 31, 35, 39)

# the same layout at a size the interpreter runs: layers 1 and 4 MLA
TINY_HP = dict(
    DEPLOYMENT, hidden_size=64, num_hidden_layers=5, first_k_dense_replace=1,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, qk_head_dim=24,
    vocab_size=512, num_nextn_predict_layers=2, full_attention_layers=[1, 4],
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16)
# a prefill of three chunks and a decode whose latent cache (12.9 GB an MLA
# block) fills a chip while the Gated DeltaNet state stays small
TINY_SHAPES = (ShapeSpec("prefill", "prefill", 150, 2),
               ShapeSpec("decode", "decode", 2**22, 64))


# -- the configuration at its published widths -----------------------------------


def test_published_parameter_count():
    cfg = get_config(ARCH)
    assert cfg.n_params == pytest.approx(432e9, rel=0.01)
    assert cfg.linear_attn_layers == tuple(i for i in range(40) if i not in FULL)
    # the registered config is what the benchmark's deployment file builds
    built, _ = program_objects(DEPLOYMENT)
    assert cfg.n_params == built.n_params
    # the main model in the graph: every node but head, plus the final
    # norm and the output head; the estimate leaves out the latent norms
    # (10 x 2,048), the final norm and the routers' biases (37 x 256)
    g = arch_layer_graph(cfg, 1, 1, kv_len=1)
    d, V = cfg.d_model, cfg.vocab
    main = sum(n.param_count for n in g.nodes[:-1]) + d + V * d
    assert main - cfg.n_params == 10 * 2048 + d + 37 * 256


# prefill; decode below the ~3,641-token crossover; decodes at 131,072 and
# the published context of 262,144
SHAPES = [(2, 65_536, None), (1, 4_097, None), (8, 1, 2_048), (64, 1, 131_072),
          (256, 1, 262_144)]


@pytest.mark.parametrize("batch,seq,kv_len", SHAPES)
def test_layer_table_matches_the_reference(batch, seq, kv_len):
    g = arch_layer_graph(get_config(ARCH), batch, seq, kv_len=kv_len)
    table = layer_table(DEPLOYMENT, batch, seq, kv_len)
    assert [n.name for n in g.nodes] == [r["name"] for r in table]
    assert g.num_layers == 42
    fields = {"flops": "flops", "param_count": "resident",
              "params_read": "streamed", "cache_elems": "cache",
              "cache_read_elems": "cache_read", "state_elems": "state",
              "state_rw_elems": "state_rw", "out_elems": "out",
              "work_elems": "work"}
    for n, r in zip(g.nodes, table):
        for mine, theirs in fields.items():
            assert getattr(n, mine) == pytest.approx(r[theirs], rel=1e-12, abs=0), (
                n.name, mine)
    # times and held bytes: the state in float32, the cache in bf16
    dep = HybridDeployment(DEPLOYMENT)
    key = ("decode", kv_len, batch) if kv_len else ("prefill", seq, batch)
    prof = tpu_cost_profile(g, state_dtype_bytes=4)
    np.testing.assert_allclose([lc.t_infer_s for lc in prof.layers],
                               dep.layer_seconds(dep.table(key)), rtol=1e-12, atol=0)
    np.testing.assert_allclose(
        [lc.param_bytes for lc in prof.layers],
        [2 * r["resident"] + 2 * r["cache"] + 4 * r["state"] for r in table],
        rtol=1e-12, atol=0)


def test_kinds_of_layer():
    cfg = get_config(ARCH)
    short = arch_layer_graph(cfg, 8, 1, kv_len=2_048)
    long = arch_layer_graph(cfg, 8, 1, kv_len=262_144)
    blocks = range(1, 41)
    mla = [i for i in blocks if short.nodes[i].cache_elems]
    assert [i - 1 for i in mla] == list(FULL)
    state = 8 * (64 * 128 * 128 + 3 * (2 * 32 * 128 + 64 * 128))
    for i in blocks:
        s, l = short.nodes[i], long.nodes[i]
        if i - 1 in FULL:  # the latent cache grows with the KV length
            assert s.state_elems == l.state_elems == 0
            assert s.cache_elems == 8 * 2_048 * 576
            assert l.cache_elems == 128 * s.cache_elems == l.cache_read_elems
        else:  # the recurrent state does not; a decode reads and writes it
            assert s.cache_elems == l.cache_elems == 0
            assert s.state_elems == l.state_elems == state
            assert s.state_rw_elems == 2 * state
    # only the two MTP blocks' MLA caches sit on head; embed holds nothing
    assert long.nodes[-1].cache_elems == 2 * 8 * 262_144 * 576
    assert long.nodes[-1].state_elems == long.nodes[0].cache_elems == 0
    # a prefill writes the state once
    pre = arch_layer_graph(cfg, 2, 4_096)
    assert pre.nodes[1].state_rw_elems == pre.nodes[1].state_elems == state // 4
    # MTP's dense FFN: each module's block reads every weight it holds, so
    # head reads what it holds plus the embedding rows and the output head
    # once more for each of the 2 modules
    cfg_rows = experts_touched(cfg.vocab, 1, 2 * 4_096) * cfg.d_model
    head = pre.nodes[-1]
    assert head.params_read - head.param_count == pytest.approx(
        2 * cfg_rows, rel=1e-12)


def test_crossover_of_state_and_cache():
    """Per sequence and layer, the 4.19 MB float32 delta-rule state equals
    576 bf16 latent values a token at about 3,641 tokens; with the conv's
    last inputs the held state crosses the cache at about 3,812."""
    assert 64 * 128 * 128 * 4 / (576 * 2) == pytest.approx(3640.9, abs=0.1)
    cfg = get_config(ARCH)
    for kv, state_holds_more in ((2_048, True), (3_800, True), (3_825, False),
                                 (262_144, False)):
        g = arch_layer_graph(cfg, 1, 1, kv_len=kv)
        state = g.nodes[1].state_elems * 4  # layer_0, Gated DeltaNet
        cache = g.nodes[4].cache_elems * 2  # layer_3, MLA
        assert (state > cache) == state_holds_more, kv
    assert cache == 262_144 * 576 * 2 > 60 * state  # 302 MB against 4.39 MB


def test_memory_cliff_at_published_widths():
    """8-chip stages cannot hold the model on 7 stages or fewer: its bf16
    weights alone exceed 8 N 0.9 16 GiB below N = 7, and its layers do
    not pack into 7."""
    cfg = get_config(ARCH)
    shape = ShapeSpec("decode", "decode", 4096, 8)
    weights = arch_layer_graph(cfg, 8, 1, kv_len=4096).total_params * 2
    for n in range(2, 7):
        assert weights > 8 * n * 0.9 * 16 * 1024**3
    grid = pipeline_grid(cfg, [shape], (8, 16, 32), range(2, 10),
                         {"ici": TPU_LINKS["ici"]})
    feasible = {(r.scenario.mix, r.scenario.n_devices): r.feasible
                for r in sweep(grid).rows}
    assert [n for n in range(2, 10) if feasible[("x8", n)]] == [8, 9]
    assert [n for n in range(2, 10) if feasible[("x16", n)]] == list(range(4, 10))
    assert all(feasible[("x32", n)] for n in range(2, 10))


def test_deepseek_v3_graph_unchanged():
    """DeepSeek-V3's graph and cost profile, as digested before the
    hybrid layout existed."""
    h = hashlib.sha256()
    cfg = get_config("deepseek-v3")
    h.update(repr(cfg.n_params).encode())
    for b, s, kv in ((4, 512, None), (2, 1, 8192), (1, 65536, None),
                     (256, 1, 262144)):
        g = arch_layer_graph(cfg, b, s, kv_len=kv)
        for n in g.nodes:
            assert n.state_elems == n.state_rw_elems == 0
            h.update(repr((n.name, n.flops, n.param_count, n.out_elems,
                           n.work_elems, n.params_read, n.cache_elems,
                           n.cache_read_elems)).encode())
        for lc in tpu_cost_profile(g).layers:
            h.update(repr((lc.name, lc.t_infer_s, lc.act_bytes, lc.param_bytes,
                           lc.work_bytes, lc.flops)).encode())
    assert h.hexdigest()[:16] == "15374278175d53fe"


def test_reduced_variant_runs_attention_only():
    small = get_config(ARCH).reduced()
    assert small.linear_attn_layers == () and small.use_mla
    assert small.n_layers == 2 and small.linear_n_v_heads == 4


def test_linear_layers_need_their_widths():
    cfg = get_config(ARCH)
    with pytest.raises(ValueError, match="outside"):
        cfg.reduced(linear_attn_layers=(0, 2))
    with pytest.raises(ValueError, match="heads"):
        cfg.reduced(linear_attn_layers=(0,), linear_n_k_heads=0)


# -- the normal path on a GigaChat3.5-shaped model --------------------------------


def _tiny_grid():
    model, links = program_objects(TINY_HP)
    return pipeline_grid(model, TINY_SHAPES, (1, 2), (2, 3, 4, 5), links,
                         loss_p=(None, 5e-4), rate_scale=(1.0, 0.25))


def _fields(row):
    return (row.scenario, row.splits, row.feasible, row.objective_cost_s,
            row.total_latency_s, row.device_s, row.transmission_s,
            row.accuracy_proxy)


@pytest.fixture(scope="module")
def numpy_rows():
    return sweep(_tiny_grid(), backend="numpy").rows


def test_tiny_grid_shape():
    grid = _tiny_grid()
    assert grid.objective == "bottleneck" and grid.mix_names == ("x1", "x2")
    assert grid.size == 2 * 2 * 4 * 2 * 2 * 2
    for p in grid.models.values():
        assert [lc.name for lc in p.layers] == [
            "embed", *(f"layer_{i}" for i in range(5)), "head"]


def test_tiny_fused_pallas_matches_the_numpy_oracle(numpy_rows):
    with jax.enable_x64(True):  # pallas then runs float64, as numpy does
        rows = sweep(_tiny_grid(), backend="pallas").rows
    assert [_fields(r) for r in rows] == [_fields(r) for r in numpy_rows]
    feasible = [r.feasible for r in rows]
    assert 0 < feasible.count(False) < feasible.count(True)


def test_tiny_plans_are_the_reference_dps(numpy_rows):
    dep = HybridDeployment(TINY_HP)
    shapes = {s.name: s for s in TINY_SHAPES}
    for row in numpy_rows:
        sc = row.scenario
        s = shapes[sc.model]
        key = (s.kind, s.seq_len, s.global_batch)
        lk = dep.link(sc.protocol, sc.loss_p, sc.rate_scale)
        local, tx = dep.local(key, int(sc.mix[1:])), dep.airtime(key, lk)
        dps, _ = bottleneck_tables(local + tx[None, :], sc.n_devices)
        best = float(dps[sc.n_devices - 1, -1])
        assert row.feasible == math.isfinite(best), sc
        if row.feasible:
            assert price(local, tx, row.splits)[0] == pytest.approx(best, rel=1e-12)
            assert row.objective_cost_s == pytest.approx(best, rel=1e-12)
