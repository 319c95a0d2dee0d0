"""Closed-loop pipeline-stage what-ifs of an MLA + MoE model with
DeepSeek Sparse Attention and IndexShare: ``pipeline_loop``'s operator
client, traffic and comparison, with the model's own configuration and
reference.

The traffic file (``"driver": "dsa_pipeline_loop"``) has
``pipeline_loop``'s layout. The deployment file gives the published
``config.json`` keys (``index_*``, ``indexer_types`` and
``mlp_layer_types`` among them), the stage device and the links. The
sampled rows of every call are compared after the window with the
float64 reference of ``bench/reference/dsa_pipeline.py``.
"""

from __future__ import annotations

from bench.drivers import pipeline_loop
from bench.reference.dsa_pipeline import DSADeployment


def program_objects(cfg: dict):
    """The program's model configuration and links, built from the
    deployment file's published hyper-parameters."""
    from repro.core.latency import LinkProfile
    from repro.models.config import ModelConfig

    n = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    if cfg["mlp_layer_types"] != ["dense"] * dense + ["sparse"] * (n - dense):
        raise ValueError("mlp_layer_types is not a dense prefix of "
                         "first_k_dense_replace layers")
    model = ModelConfig(
        name=cfg["model_type"], family="moe", n_layers=n,
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["qk_head_dim"],
        n_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        first_k_dense=dense, moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_mtp_modules=cfg["num_nextn_predict_layers"], use_mla=True,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        index_shared_layers=tuple(i for i, kind in enumerate(cfg["indexer_types"])
                                  if kind == "shared"),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])
    links = {k: LinkProfile(**v) for k, v in cfg["links"].items()}
    return model, links


class Driver(pipeline_loop.Driver):
    """``pipeline_loop.Driver`` on the DSA model and reference."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        super().__init__(cfg, traffic, seed)
        self.traffic.dep = DSADeployment(cfg)

    def setup(self) -> None:
        from repro.core.planner import pipeline_grid
        from repro.core.sweep import sweep

        self.pipeline_grid, self.sweep = pipeline_grid, sweep
        self.model, self.links = program_objects(self.cfg)
        # the warm call uses the spare last draw: same shapes as the window
        self._sweep(self._grid(self.traffic.grids[-1]))
