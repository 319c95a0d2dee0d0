"""DeepSeek-V3 as pipeline stages on v5e chip groups, against the plain
float64 reference in ``tests/_ref_deepseek_v3.py``.

The layer table at the published widths, the parameter totals, the
expert-touch count, the memory cliff, and the planner's normal path:
``sweep(pipeline_grid(...))`` on numpy and interpret-mode Pallas,
bit-identical to the scalar oracle and at zero regret against the
reference's bottleneck DP."""

from __future__ import annotations

import hashlib
import math

import jax
import numpy as np
import pytest

import _ref_deepseek_v3 as ref
from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import ShapeSpec
from repro.core.planner import pipeline_grid, tpu_cost_profile
from repro.core.profiles import TPU_LINKS
from repro.core.sweep import sweep, sweep_scalar
from repro.models.config import ModelConfig
from repro.models.graph import arch_layer_graph, experts_touched

# DeepSeek-V3 config.json (huggingface.co/deepseek-ai/DeepSeek-V3)
PUBLISHED = dict(
    hidden_size=7168, num_hidden_layers=61, first_k_dense_replace=3,
    intermediate_size=18432, moe_intermediate_size=2048,
    n_routed_experts=256, num_experts_per_tok=8, n_shared_experts=1,
    num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    vocab_size=129280, num_nextn_predict_layers=1)

# the same layout at a size the scalar oracle and the interpreter run
TINY_HP = dict(
    hidden_size=64, num_hidden_layers=4, first_k_dense_replace=1,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
    num_nextn_predict_layers=1)
TINY = ModelConfig(
    name="tiny-dsv3", family="moe", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=128, vocab=512, head_dim=24, n_experts=8, top_k=2,
    first_k_dense=1, moe_d_ff=32, n_shared_experts=1, n_mtp_modules=1,
    use_mla=True, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16)
# a prefill, and a decode whose latent cache (12.9 GB a block) fills a chip
TINY_SHAPES = (ShapeSpec("prefill", "prefill", 64, 2),
               ShapeSpec("decode", "decode", 2**22, 64))


def _shape_args(shape):
    if shape.kind == "decode":
        return shape.global_batch, 1, shape.seq_len
    return shape.global_batch, shape.seq_len, None


# -- the configuration at its published widths -----------------------------------


def test_published_parameter_totals():
    tot = ref.param_totals(PUBLISHED)
    assert tot["main"] == pytest.approx(671e9, rel=0.01)
    assert tot["active"] == pytest.approx(37e9, rel=0.03)
    assert tot["mtp_own"] == pytest.approx(11.6e9, rel=0.05)
    cfg = get_config("deepseek-v3")
    # the config's estimate leaves out the small norms and biases
    assert cfg.n_params == pytest.approx(tot["main"], rel=1e-6)
    g = arch_layer_graph(cfg, 1, 1, kv_len=1)
    # main model: every node but head, plus the final norm and the head
    d, V = cfg.d_model, cfg.vocab
    head_main = d + V * d
    assert sum(n.param_count for n in g.nodes[:-1]) + head_main == tot["main"]
    # head also holds the MTP module's own weights and the embedding copy
    assert g.nodes[-1].param_count == head_main + tot["mtp_own"] + V * d


@pytest.mark.parametrize("batch,seq,kv_len", [(4, 16_384, None), (2, 8192, None),
                                              (8, 1, 4096), (256, 1, 131_072)])
def test_layer_table_matches_the_reference(batch, seq, kv_len):
    g = arch_layer_graph(get_config("deepseek-v3"), batch, seq, kv_len=kv_len)
    table = ref.layer_table(PUBLISHED, batch, seq, kv_len)
    assert [n.name for n in g.nodes] == [r["name"] for r in table]
    assert g.num_layers == 63
    fields = {"flops": "flops", "param_count": "resident",
              "params_read": "streamed", "cache_elems": "cache",
              "cache_read_elems": "cache_read", "out_elems": "out",
              "work_elems": "work"}
    for n, r in zip(g.nodes, table):
        for mine, theirs in fields.items():
            assert getattr(n, mine) == pytest.approx(r[theirs], rel=1e-12, abs=0), (
                n.name, mine)
    prof = tpu_cost_profile(g)
    np.testing.assert_allclose([lc.t_infer_s for lc in prof.layers],
                               ref.layer_seconds(table), rtol=1e-12, atol=0)


def test_kinds_of_layer():
    g = arch_layer_graph(get_config("deepseek-v3"), 8, 1, kv_len=4096)
    dense, moe = g.nodes[1], g.nodes[4]  # layer_0, layer_3
    assert dense.param_count < 1e9 < 1.1e10 < moe.param_count
    # a decode step of 8 sequences reads about 57 of the 256 experts
    assert moe.params_read < 0.3 * moe.param_count
    assert dense.params_read == dense.param_count
    latent = 8 * 4096 * (512 + 64)
    assert all(n.cache_elems == n.cache_read_elems == latent
               for n in g.nodes[1:-1])
    assert g.nodes[-1].cache_elems == latent  # the MTP block's cache
    assert g.nodes[0].cache_elems == 0


def test_layout_without_mla_is_refused():
    # a GQA MoE with a shared expert would otherwise get MLA's formulas at
    # zero latent ranks: attention with no weights and no cache
    gqa = ModelConfig(
        name="tiny-gqa-shared", family="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=512, head_dim=16,
        n_experts=8, top_k=2, moe_d_ff=32, n_shared_experts=1)
    with pytest.raises(ValueError, match="MLA"):
        arch_layer_graph(gqa, 1, 16)


def test_experts_touched():
    assert experts_touched(256, 8, 1) == pytest.approx(8, rel=1e-12)
    assert experts_touched(256, 8, 8) == pytest.approx(57.42, abs=0.01)
    assert experts_touched(256, 8, 128) == pytest.approx(251.6, abs=0.01)
    assert experts_touched(256, 8, 4 * 2048) == 256.0  # a prefill reads all
    cfg = get_config("deepseek-v3")
    prefill = arch_layer_graph(cfg, 1, 2048).nodes[10]
    assert prefill.params_read == prefill.param_count
    one = arch_layer_graph(cfg, 1, 1, kv_len=1).nodes[10]
    expert = 3 * cfg.d_model * cfg.moe_d_ff
    assert one.param_count - one.params_read == pytest.approx(
        (256 - 8) * expert, rel=1e-12)


def test_memory_cliff_at_published_widths():
    """8-chip stages cannot hold the model on 10 stages or fewer: its
    bf16 weights alone exceed 8 N 0.9 16 GiB."""
    cfg = get_config("deepseek-v3")
    shape = ShapeSpec("decode", "decode", 4096, 8)
    weights = arch_layer_graph(cfg, 8, 1, kv_len=4096).total_params * 2
    for n in range(2, 11):
        assert weights > 8 * n * 0.9 * 16 * 1024**3
    grid = pipeline_grid(cfg, [shape], (8,), range(2, 13), {"ici": TPU_LINKS["ici"]})
    rows = sweep(grid).rows
    feasible = {r.scenario.n_devices: r.feasible for r in rows}
    assert not any(feasible[n] for n in range(2, 11))
    assert feasible[12]


def test_existing_graphs_unchanged():
    """Each graph and cost profile of the configurations that predate
    DeepSeek-V3, at one prefill and one decode shape, as digested before
    the DeepSeek-V3 layout existed."""
    want = {
        "granite-moe-1b-a400m": "f2c0cb8bb4e07d84",
        "qwen3-moe-235b-a22b": "04eb8aa9b27c941c",
        "zamba2-1.2b": "e7692ad14bc5932d",
        "musicgen-medium": "b224b1df25070d20",
        "deepseek-7b": "7ad90de5633291d2",
        "stablelm-12b": "a9bbf1cc50943229",
        "minicpm3-4b": "2bf5cd7cdb83d6de",
        "granite-34b": "73bdf8783b127102",
        "qwen2-vl-72b": "3880f485e2ff149c",
        "xlstm-1.3b": "577473ce75fed795",
    }
    assert set(want) == set(ARCH_IDS) - {"deepseek-v3", "gigachat3.5-432b-a28b",
                                         "glm-5.2"}
    for arch, digest in want.items():
        h = hashlib.sha256()
        cfg = get_config(arch)
        h.update(repr(cfg.n_params).encode())
        for b, s, kv in ((4, 512, None), (2, 1, 8192)):
            g = arch_layer_graph(cfg, b, s, kv_len=kv)
            for n in g.nodes:
                assert n.streamed_params is None and n.cache_elems == 0
                h.update(repr((n.name, n.flops, n.param_count, n.out_elems,
                               n.work_elems)).encode())
            for lc in tpu_cost_profile(g).layers:
                h.update(repr((lc.name, lc.t_infer_s, lc.act_bytes,
                               lc.param_bytes, lc.work_bytes,
                               lc.flops)).encode())
        assert h.hexdigest()[:16] == digest, arch


# -- the normal path on a DeepSeek-shaped model ----------------------------------


def _tiny_grid():
    return pipeline_grid(TINY, TINY_SHAPES, (1, 2), (2, 3, 4, 5), TPU_LINKS,
                         loss_p=(None, 5e-4), rate_scale=(1.0, 0.25))


def test_tiny_grid_shape():
    grid = _tiny_grid()
    assert grid.objective == "bottleneck"
    assert set(grid.models) == {"prefill", "decode"}
    assert grid.mix_names == ("x1", "x2")
    assert grid.size == 2 * 2 * 4 * 2 * 2 * 2
    for p in grid.models.values():
        assert [lc.name for lc in p.layers] == [
            "embed", "layer_0", "layer_1", "layer_2", "layer_3", "head"]


def _fields(row):
    return (row.scenario, row.splits, row.feasible, row.objective_cost_s,
            row.total_latency_s, row.device_s, row.transmission_s)


@pytest.fixture(scope="module")
def scalar_rows():
    return sweep_scalar(_tiny_grid()).rows


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_tiny_sweep_matches_the_scalar_oracle(backend, scalar_rows):
    with jax.enable_x64(True):  # pallas then runs float64, as numpy does
        rows = sweep(_tiny_grid(), backend=backend).rows
    assert [_fields(r) for r in rows] == [_fields(r) for r in scalar_rows]
    # the memory cliff shows: some plans are infeasible, most are not
    feasible = [r.feasible for r in rows]
    assert 0 < feasible.count(False) < feasible.count(True)


def test_tiny_sweep_has_zero_regret(scalar_rows):
    rows = sweep(_tiny_grid(), backend="numpy").rows
    grid = _tiny_grid()
    shapes = {s.name: s for s in TINY_SHAPES}
    for row in rows:
        sc = row.scenario
        link = grid.link_variant(sc)
        table = ref.layer_table(TINY_HP, *_shape_args(shapes[sc.model]))
        C = ref.segment_costs(table, int(sc.mix[1:]), vars(link))
        best, _ = ref.bottleneck_dp(C, sc.n_devices)
        assert row.feasible == math.isfinite(best), sc
        if row.feasible:
            assert ref.plan_cost(C, row.splits) - best == 0.0, sc
            assert row.objective_cost_s == best, sc
