"""gigachat3.5-432b-a28b — 40L d7168 hybrid: 10 MLA layers (3, 7, ..., 39;
64H, q_lora=1536, kv_lora=512, qk_nope=128, qk_rope=64, v=128, a sigmoid
output gate) and 30 Gated DeltaNet layers (32 key and 64 value heads of
128, conv 4, a float32 recurrent state); a norm before and after each
sublayer; first 3 layers dense SwiGLU d_ff=18432, then 37 MoE layers of
256 routed experts (width 2048, top-8) plus 1 shared expert; vocab
128256, untied; two multi-token-prediction modules with a dense FFN
(nextn_is_sparse false).
[hf:ai-sage/GigaChat3.5-432B-A28B, config.json]

Planned as pipeline stages on v5e chip groups (``planner.pipeline_grid``);
the JAX model in ``models/transformer.py`` runs its reduced variant only,
on the MLA + MoE path (it has no Gated DeltaNet, output gate, pre/post
norms, dense prefix, shared expert or MTP path)."""

from repro.models.config import ModelConfig

FULL_ATTENTION_LAYERS = (3, 7, 11, 15, 19, 23, 27, 31, 35, 39)

config = ModelConfig(
    name="gigachat3.5-432b-a28b",
    family="hybrid",
    n_layers=40,
    d_model=7168,
    n_heads=64,
    n_kv_heads=64,
    d_ff=18432,
    vocab=128256,
    head_dim=192,  # qk_head_dim (bookkeeping only; MLA paths use the split dims)
    n_experts=256,
    top_k=8,
    first_k_dense=3,
    moe_d_ff=2048,
    n_shared_experts=1,
    n_mtp_modules=2,
    mtp_dense=True,
    use_mla=True,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    attn_output_gate=True,
    linear_attn_layers=tuple(i for i in range(40)
                             if i not in FULL_ATTENTION_LAYERS),
    linear_n_k_heads=32,
    linear_n_v_heads=64,
    linear_k_head_dim=128,
    linear_v_head_dim=128,
    linear_conv_kernel=4,
    linear_state_dtype="float32",
    rope_theta=100_000.0,
    pre_post_norm=True,
    gated_mlp=True,
    norm_eps=1e-6,
    tie_embeddings=False,
)
