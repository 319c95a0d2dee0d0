"""glm-5.2 — 78L d6144 64H MLA (q_lora=2048, kv_lora=512, qk_nope=192,
qk_rope=64, v=256) with DeepSeek Sparse Attention: a lightning indexer of
32 heads of 128 picks the top 2,048 cached tokens MLA attends to. Under
IndexShare only layers 0-2 and every fourth layer after them (6, 10, ...,
74) run the indexer; the other 57 reuse the nearest earlier selection.
First 3 layers dense SwiGLU d_ff=12288, then 75 MoE layers of 256 routed
experts (width 2048, top-8) plus 1 shared expert; vocab 154880, untied;
one multi-token-prediction module.
[hf:zai-org/GLM-5.2, config.json]

Planned as pipeline stages on v5e chip groups (``planner.pipeline_grid``);
the JAX model in ``models/transformer.py`` runs its reduced variant only,
on the dense MLA + MoE path (it has no indexer, dense prefix, shared
expert or MTP path)."""

from repro.models.config import ModelConfig

# indexer_types "full": the layers that run their own indexer
INDEX_FULL_LAYERS = (0, 1, 2, *range(6, 78, 4))

config = ModelConfig(
    name="glm-5.2",
    family="moe",
    n_layers=78,
    d_model=6144,
    n_heads=64,
    n_kv_heads=64,
    d_ff=12288,
    vocab=154880,
    head_dim=256,  # qk_head_dim (bookkeeping only; MLA paths use the split dims)
    n_experts=256,
    top_k=8,
    first_k_dense=3,
    moe_d_ff=2048,
    n_shared_experts=1,
    n_mtp_modules=1,
    use_mla=True,
    q_lora_rank=2048,
    kv_lora_rank=512,
    qk_nope_head_dim=192,
    qk_rope_head_dim=64,
    v_head_dim=256,
    index_n_heads=32,
    index_head_dim=128,
    index_topk=2048,
    index_shared_layers=tuple(i for i in range(78) if i not in INDEX_FULL_LAYERS),
    rope_theta=8_000_000.0,
    gated_mlp=True,
    norm_eps=1e-5,
    tie_embeddings=False,
)
