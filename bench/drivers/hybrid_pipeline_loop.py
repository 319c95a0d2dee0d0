"""Closed-loop pipeline-stage what-ifs of a hybrid MLA + Gated DeltaNet
model: ``pipeline_loop``'s operator client, traffic and comparison, with
the hybrid's own model configuration and reference.

The traffic file (``"driver": "hybrid_pipeline_loop"``) has
``pipeline_loop``'s layout. The deployment file gives the published
``config.json`` keys (``full_attention_layers`` and the ``linear_*``
keys among them), the state dtype, the stage device and the links. The
sampled rows of every call are compared after the window with the
float64 reference of ``bench/reference/hybrid_pipeline.py``.
"""

from __future__ import annotations

from bench.drivers import pipeline_loop
from bench.reference.hybrid_pipeline import HybridDeployment


def program_objects(cfg: dict):
    """The program's model configuration and links, built from the
    deployment file's published hyper-parameters."""
    from repro.core.latency import LinkProfile
    from repro.models.config import ModelConfig

    n = cfg["num_hidden_layers"]
    full = set(cfg["full_attention_layers"])
    model = ModelConfig(
        name=cfg["model_type"], family="hybrid", n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["qk_head_dim"],
        n_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        first_k_dense=cfg["first_k_dense_replace"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_mtp_modules=cfg["num_nextn_predict_layers"],
        mtp_dense=not cfg["nextn_is_sparse"], use_mla=True,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        attn_output_gate=cfg["gated_attention"],
        linear_attn_layers=tuple(i for i in range(n) if i not in full),
        linear_n_k_heads=cfg["linear_num_key_heads"],
        linear_n_v_heads=cfg["linear_num_value_heads"],
        linear_k_head_dim=cfg["linear_key_head_dim"],
        linear_v_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        linear_state_dtype=cfg["state_dtype"],
        pre_post_norm=cfg["layernorm_type"] == "pre_post",
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])
    links = {k: LinkProfile(**v) for k, v in cfg["links"].items()}
    return model, links


class Driver(pipeline_loop.Driver):
    """``pipeline_loop.Driver`` on the hybrid's model and reference."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        super().__init__(cfg, traffic, seed)
        self.traffic.dep = HybridDeployment(cfg)

    def setup(self) -> None:
        from repro.core.planner import pipeline_grid
        from repro.core.sweep import sweep

        self.pipeline_grid, self.sweep = pipeline_grid, sweep
        self.model, self.links = program_objects(self.cfg)
        # the warm call uses the spare last draw: same shapes as the window
        self._sweep(self._grid(self.traffic.grids[-1]))
