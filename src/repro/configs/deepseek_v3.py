"""deepseek-v3 — 61L d7168 128H MLA (q_lora=1536, kv_lora=512, qk_nope=128,
qk_rope=64, v=128); first 3 layers dense SwiGLU d_ff=18432, then 58 MoE
layers of 256 routed experts (width 2048, top-8, sigmoid routing) plus 1
shared expert; vocab 129280, untied; one multi-token-prediction module.
[hf:deepseek-ai/DeepSeek-V3, config.json]

Planned as pipeline stages on v5e chip groups (``planner.pipeline_grid``);
the JAX model in ``models/transformer.py`` runs its reduced variant only
(it has no dense prefix, shared expert or MTP path)."""

from repro.models.config import ModelConfig

config = ModelConfig(
    name="deepseek-v3",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,
    vocab=129280,
    head_dim=192,  # qk_nope + qk_rope (bookkeeping only; MLA paths use the split dims)
    n_experts=256,
    top_k=8,
    first_k_dense=3,
    moe_d_ff=2048,
    n_shared_experts=1,
    n_mtp_modules=1,
    use_mla=True,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    gated_mlp=True,
    norm_eps=1e-6,
    tie_embeddings=False,
)
