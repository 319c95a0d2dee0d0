"""GLM-5.2 (MLA with DeepSeek Sparse Attention and IndexShare) as pipeline
stages on v5e chip groups, against the plain float64 reference in
``bench/reference/dsa_pipeline.py``.

The parameter count, the layer table at the published widths, the three
kinds of layer (dense prefix, full indexer, shared selection), the cache
a decode step reads against the cache a layer holds, the indices a cut
before a shared layer ships, the memory cliff, the configuration's
refusals, and the planner's normal path: ``sweep(pipeline_grid(...))``
with the fused Pallas kernel (interpret mode) equal to the numpy oracle
in every row field, and the oracle's plans equal to the reference
bottleneck DP's. DeepSeek-V3's and GigaChat3.5's graphs are pinned by
digest."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.drivers.dsa_pipeline_loop import program_objects
from bench.reference.dsa_pipeline import DSADeployment, layer_table
from bench.reference.pipeline import bottleneck_tables, price
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.core.planner import pipeline_grid, tpu_cost_profile
from repro.core.profiles import TPU_LINKS
from repro.core.sweep import sweep
from repro.models.graph import arch_layer_graph

ARCH = "glm-5.2"
DEPLOYMENT = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                         / "glm52-v5e-pp.json").read_text())
FULL = (0, 1, 2, *range(6, 78, 4))
SHARED = tuple(i for i in range(78) if i not in FULL)
LATENT = 512 + 64  # kv_lora + qk_rope

# the same layout at a size the interpreter runs: layers 0, 1 and 4 run
# the indexer, 2, 3 and 5 share it; top-16 of 150 prefill tokens
TINY_HP = dict(
    DEPLOYMENT, hidden_size=64, num_hidden_layers=6, first_k_dense_replace=1,
    mlp_layer_types=["dense"] + ["sparse"] * 5,
    indexer_types=["full", "full", "shared", "shared", "full", "shared"],
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, qk_head_dim=24,
    vocab_size=512, index_n_heads=2, index_head_dim=8, index_topk=16)
# a prefill past the top-k, and a decode whose cache (12.9 GB of latents
# a block, 17.2 GB with a full layer's index keys) fills a chip while a
# shared layer reads 16 latents a sequence
TINY_SHAPES = (ShapeSpec("prefill", "prefill", 150, 2),
               ShapeSpec("decode", "decode", 2**22, 64))


# -- the configuration at its published widths -----------------------------------


def test_published_parameter_count():
    cfg = get_config(ARCH)
    # 743.4 B for the main model, the family's published ~744 B
    assert cfg.n_params == pytest.approx(744e9, rel=0.01)
    assert cfg.index_shared_layers == SHARED
    assert cfg.index_params == 2048 * 32 * 128 + 6144 * 128 + 2 * 128 + 6144 * 32
    # the registered config is what the benchmark's deployment file builds
    built, _ = program_objects(DEPLOYMENT)
    assert cfg.n_params == built.n_params
    assert built.index_shared_layers == SHARED
    # the main model in the graph: every node but head, plus the final
    # norm and the output head; the estimate leaves out the latent norms
    # (78 x 2,560), the final norm and the routers' biases (75 x 256)
    g = arch_layer_graph(cfg, 1, 1, kv_len=1)
    d, V = cfg.d_model, cfg.vocab
    main = sum(n.param_count for n in g.nodes[:-1]) + d + V * d
    assert main - cfg.n_params == 78 * (2048 + 512) + d + 75 * 256
    # the indexer's weights sit in the 21 full layers only
    dense = dataclasses.replace(cfg, index_n_heads=0, index_head_dim=0,
                                index_topk=0, index_shared_layers=())
    assert cfg.n_params - dense.n_params == 21 * cfg.index_params


# a prefill; decodes below, at and above the top-k of 2,048; a decode at
# 64k and at the published context of 1,048,576
SHAPES = [(2, 131_072, None), (1, 8_192, None), (4, 1, 1_500), (8, 1, 2_048),
          (16, 1, 2_049), (32, 1, 65_536), (64, 1, 1_048_576)]


@pytest.mark.parametrize("batch,seq,kv_len", SHAPES)
def test_layer_table_matches_the_reference(batch, seq, kv_len):
    g = arch_layer_graph(get_config(ARCH), batch, seq, kv_len=kv_len)
    table = layer_table(DEPLOYMENT, batch, seq, kv_len)
    assert [n.name for n in g.nodes] == [r["name"] for r in table]
    assert g.num_layers == 80
    fields = {"flops": "flops", "param_count": "resident",
              "params_read": "streamed", "cache_elems": "cache",
              "cache_read_elems": "cache_read",
              "cut_index_elems": "index_out", "out_elems": "out",
              "work_elems": "work"}
    for n, r in zip(g.nodes, table):
        assert n.state_elems == n.state_rw_elems == 0
        for mine, theirs in fields.items():
            assert getattr(n, mine) == pytest.approx(r[theirs], rel=1e-12, abs=0), (
                n.name, mine)
    # times, held bytes and shipped bytes: bf16 values, int32 indices
    dep = DSADeployment(DEPLOYMENT)
    key = ("decode", kv_len, batch) if kv_len else ("prefill", seq, batch)
    prof = tpu_cost_profile(g)
    np.testing.assert_allclose([lc.t_infer_s for lc in prof.layers],
                               dep.layer_seconds(dep.table(key)), rtol=1e-12, atol=0)
    np.testing.assert_allclose(
        [lc.param_bytes for lc in prof.layers],
        [2 * r["resident"] + 2 * r["cache"] for r in table], rtol=1e-12, atol=0)
    assert [lc.act_bytes for lc in prof.layers] == [
        2 * r["out"] + 4 * r["index_out"] for r in table]


def test_kinds_of_layer():
    """3 dense layers, 21 that run the indexer, 57 that share it: at a
    128k prefill the three differ in time, and both kinds of MoE layer
    from the dense prefix."""
    cfg = get_config(ARCH)
    assert sum(not cfg.is_moe_layer(i) for i in range(78)) == 3
    assert len(FULL) == 21 and len(SHARED) == 57
    g = arch_layer_graph(cfg, 1, 131_072)
    prof = tpu_cost_profile(g)
    t = [lc.t_infer_s for lc in prof.layers]
    dense, full, shared = t[1], t[7], t[4]  # layer_0, layer_6, layer_3
    assert len({dense, full, shared}) == 3
    assert {t[1 + i] for i in FULL if i >= 3} == {full}
    assert {t[1 + i] for i in SHARED} == {shared}
    # DSA's attention is top-2,048 where dense MLA would be 64 times that,
    # and the indexer's scores run in full layers only
    S, H, I = 131_072, 64, 32
    attention = 2.0 * H * S * 2048 * (LATENT + 512)
    assert attention == pytest.approx(3.7e13, rel=0.02)
    scores = 2.0 * S * S * I * (128 + 1)
    assert scores == pytest.approx(1.4e14, rel=0.02)
    assert g.nodes[7].flops - g.nodes[4].flops == pytest.approx(
        scores + 2.0 * S * (2048 * I * 128 + 6144 * 128 + 6144 * I), rel=1e-12)


def test_cache_read_against_cache_held():
    """A shared layer's decode step reads the top-2,048 latents whatever
    the cache holds: 512 times less at 1M tokens. A full layer also reads
    every index key: 3.95x less than dense MLA at 64k, 4.46x at 1M. Held
    and read differ in exactly the DSA layers, and only at decode."""
    cfg = get_config(ARCH)
    reads = []
    for kv in (4_096, 65_536, 1_048_576):
        g = arch_layer_graph(cfg, 1, 1, kv_len=kv)
        sh, fu = g.nodes[4], g.nodes[7]  # layer_3 shared, layer_6 full
        assert sh.cache_elems == kv * LATENT
        assert fu.cache_elems == kv * (LATENT + 128)
        assert fu.cache_read_elems == 2048 * LATENT + kv * 128
        reads.append(sh.cache_read_elems)
        assert g.nodes[0].cache_elems == 0
        assert all(n.cache_read_elems < n.cache_elems for n in g.nodes[1:])
    assert reads == [2048 * LATENT] * 3
    dense = lambda kv: kv * LATENT  # noqa: E731
    assert dense(65_536) / (2048 * LATENT + 65_536 * 128) == pytest.approx(
        3.945, abs=5e-4)
    big = arch_layer_graph(cfg, 1, 1, kv_len=1_048_576)
    assert big.nodes[4].cache_elems == 512 * big.nodes[4].cache_read_elems
    assert dense(1_048_576) / big.nodes[7].cache_read_elems == pytest.approx(
        4.461, abs=5e-4)
    # 96.6 KB a token held across the 79 MLA blocks (MTP's included)
    assert sum(n.cache_elems for n in big.nodes) * 2 == 96_640 * 1_048_576
    # below the top-k a decode reads everything it holds; a prefill
    # reads its held cache once
    short = arch_layer_graph(cfg, 1, 1, kv_len=1_500)
    pre = arch_layer_graph(cfg, 2, 8_192)
    for g in (short, pre):
        assert all(n.cache_read_elems == n.cache_elems for n in g.nodes)
    # DeepSeek-V3 (dense MLA) reads what it holds at every length
    dsv3 = arch_layer_graph(get_config("deepseek-v3"), 1, 1, kv_len=1_048_576)
    assert all(n.cache_read_elems == n.cache_elems for n in dsv3.nodes)


def test_cut_index_bytes_before_shared_layers():
    """A cut ships the B x S x min(K, 2,048) int32 indices exactly when
    the next layer shares the selection: 57 of the 79 cut positions, 2/3
    more bytes than the bf16 hidden state."""
    cfg = get_config(ARCH)
    for batch, seq, kv in ((8, 1, 1_500), (8, 1, 300_000), (2, 16_384, None)):
        g = arch_layer_graph(cfg, batch, seq, kv_len=kv)
        picked = batch * seq * min(seq if kv is None else kv, 2048)
        ships = [i for i, n in enumerate(g.nodes) if n.cut_index_elems]
        assert ships == list(SHARED)  # node i is layer_{i-1}
        assert {g.nodes[i].cut_index_elems for i in ships} == {picked}
        prof = tpu_cost_profile(g)
        for i in ships:
            assert prof.layers[i].act_bytes == 2 * batch * seq * 6144 + 4 * picked
    hidden = 2 * 6144
    assert 4 * 2048 / hidden == pytest.approx(2 / 3)


def test_memory_cliff_at_published_widths():
    """The 1.51 TB of bf16 weights rule out 8-chip stages below 13,
    16-chip below 7 and 32-chip below 4; 64 sequences at 1M tokens hold
    more cache than 16 stages of 32 chips have after the weights."""
    cfg = get_config(ARCH)
    weights = arch_layer_graph(cfg, 4, 1, kv_len=4096).total_params * 2
    assert weights == pytest.approx(1.51e12, rel=0.01)
    for chips, least in ((8, 13), (16, 7), (32, 4)):
        assert weights > chips * (least - 1) * 0.9 * 16 * 1024**3
    shapes = (ShapeSpec("short", "decode", 4096, 4),
              ShapeSpec("long", "decode", 1_048_576, 8),
              ShapeSpec("full", "decode", 1_048_576, 64))
    grid = pipeline_grid(cfg, shapes, (8, 16, 32), range(2, 17),
                         {"ici": TPU_LINKS["ici"]})
    feasible = {}
    for r in sweep(grid).rows:
        sc = r.scenario
        feasible.setdefault((sc.model, sc.mix), []).extend(
            [sc.n_devices] if r.feasible else [])
    assert feasible[("short", "x8")] == list(range(13, 17))
    assert feasible[("short", "x16")] == list(range(7, 17))
    assert feasible[("short", "x32")] == list(range(4, 17))
    assert feasible[("long", "x8")] == []
    assert feasible[("long", "x16")] == list(range(10, 17))
    assert feasible[("long", "x32")] == list(range(5, 17))
    assert all(feasible[("full", m)] == [] for m in ("x8", "x16", "x32"))


def _digest(arch: str) -> str:
    h = hashlib.sha256()
    cfg = get_config(arch)
    h.update(repr(cfg.n_params).encode())
    for b, s, kv in ((4, 512, None), (2, 1, 8192), (1, 65536, None),
                     (256, 1, 262144)):
        g = arch_layer_graph(cfg, b, s, kv_len=kv)
        for n in g.nodes:
            assert n.cut_index_elems == 0
            h.update(repr((n.name, n.flops, n.param_count, n.out_elems,
                           n.work_elems, n.params_read, n.cache_elems,
                           n.cache_read_elems, n.state_elems,
                           n.state_rw_elems)).encode())
        for lc in tpu_cost_profile(g).layers:
            h.update(repr((lc.name, lc.t_infer_s, lc.act_bytes, lc.param_bytes,
                           lc.work_bytes, lc.flops)).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("arch,digest", [
    ("deepseek-v3", "b3c73027f7926f71"),
    ("gigachat3.5-432b-a28b", "3872cc2d4c829512")])
def test_dense_mla_graphs_unchanged(arch, digest):
    """DeepSeek-V3's and GigaChat3.5's graphs and cost profiles, as
    digested before sparse attention existed."""
    assert _digest(arch) == digest


def test_reduced_variant_drops_sparse_attention():
    small = get_config(ARCH).reduced()
    assert small.index_topk == 0 and small.index_shared_layers == ()
    assert small.use_mla and small.n_layers == 2


@pytest.mark.parametrize("change,match", [
    (dict(index_shared_layers=(0, 3)), "layer 0"),
    (dict(index_shared_layers=(3, 78)), "outside"),
    (dict(use_mla=False), "use_mla"),
    (dict(linear_attn_layers=(5,), linear_n_k_heads=2, linear_n_v_heads=4,
          linear_k_head_dim=16, linear_v_head_dim=16, linear_conv_kernel=4),
     "Gated DeltaNet"),
    (dict(index_n_heads=0), "heads"),
    (dict(index_topk=0), "index_topk"),
])
def test_sparse_attention_refusals(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(get_config(ARCH), **change)


# -- the normal path on a GLM-5.2-shaped model ------------------------------------


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
            "embed", *(f"layer_{i}" for i in range(6)), "head"]
    # the cuts before layers 2, 3 and 5 ship the indices
    pre = grid.models["prefill"].layers
    assert [lc.act_bytes - 2 * 2 * 150 * 64 for lc in pre[:-1]] == [
        0, 0, 4 * 2 * 150 * 16, 4 * 2 * 150 * 16, 0, 4 * 2 * 150 * 16, 0]


def test_tiny_fused_pallas_matches_the_numpy_oracle(numpy_rows):
    with jax.enable_x64(True):  # pallas then runs float64, as numpy does
        rows = sweep(_tiny_grid(), backend="pallas").rows
    assert [_fields(r) for r in rows] == [_fields(r) for r in numpy_rows]
    feasible = [r.feasible for r in rows]
    assert 0 < feasible.count(False) < feasible.count(True)


def test_tiny_plans_are_the_reference_dps(numpy_rows):
    dep = DSADeployment(TINY_HP)
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
