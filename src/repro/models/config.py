"""Model configuration for all assigned architectures.

One frozen dataclass covers the whole zoo; family-specific fields default
off. Every config in ``repro/configs/`` instantiates this with the exact
published dimensions.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Literal

Family = Literal["dense", "moe", "hybrid", "ssm", "audio", "vlm"]


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None  # default d_model // n_heads

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 1024  # tokens per dispatch group (GShard-style)
    first_k_dense: int = 0  # leading layers with a dense FFN of width d_ff
    moe_d_ff: int = 0  # routed/shared expert width; 0 -> d_ff
    n_shared_experts: int = 0  # experts every token passes through
    n_mtp_modules: int = 0  # multi-token-prediction modules after the head
    mtp_dense: bool = False  # MTP blocks take the dense FFN (nextn_is_sparse false)

    # --- MLA (MiniCPM3 / DeepSeek-V2-style latent attention) ---------------
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    attn_output_gate: bool = False  # sigmoid(x W_G) gates each head's output

    # --- Gated DeltaNet linear attention beside MLA (GigaChat3.5) ------------
    # indices of the decoder layers that are Gated DeltaNet; the rest are MLA
    linear_attn_layers: tuple[int, ...] = ()
    linear_n_k_heads: int = 0
    linear_n_v_heads: int = 0
    linear_k_head_dim: int = 0
    linear_v_head_dim: int = 0
    linear_conv_kernel: int = 0  # depthwise causal conv over q, k and v
    linear_state_dtype: str = "float32"  # the held recurrent and conv state

    # --- DeepSeek Sparse Attention over MLA (GLM-5.2) --------------------------
    # a lightning indexer of index_n_heads x index_head_dim scores every cached
    # token; MLA attends to its top index_topk (0: dense MLA). The layers in
    # index_shared_layers run no indexer and reuse the selection of the
    # nearest earlier layer that does (IndexShare)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_shared_layers: tuple[int, ...] = ()

    # --- position encoding --------------------------------------------------
    rope_theta: float = 10_000.0
    mrope_sections: tuple[int, int, int] | None = None  # qwen2-vl (t, h, w)

    # --- residual / block style ---------------------------------------------
    parallel_residual: bool = False  # stablelm-2: attn and mlp share the residual
    pre_post_norm: bool = False  # a norm before and after each sublayer (4 a block)
    gated_mlp: bool = True  # SwiGLU (False -> GELU MLP, e.g. granite-34b)
    tie_embeddings: bool = False

    # --- SSM / hybrid --------------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    d_conv: int = 4
    expand: int = 2
    # per-layer block kinds; None -> all "attn". e.g. zamba2 mixes "mamba"
    # with a shared "attn" block, xlstm mixes "mlstm"/"slstm".
    block_pattern: tuple[str, ...] | None = None
    shared_attn: bool = False  # zamba2: one shared param set for all attn blocks

    # --- modality frontends (STUBS per assignment) ---------------------------
    frontend: Literal["none", "audio_codes", "vision_embeds"] = "none"
    n_codebooks: int = 0  # musicgen: EnCodec streams

    # --- numerics -------------------------------------------------------------
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"  # activation/param dtype for the big runs
    remat: bool = True  # activation checkpointing per block (training)

    # --- distributed-training knobs (production memory levers) ---------------
    train_microbatches: int = 1  # gradient-accumulation microbatches per step
    remat_group: int = 1  # layers per remat group (boundaries saved = L/group)
    fsdp: bool = False  # shard params over the data axes too (FSDP/ZeRO-3)
    scan_chunk: int = 128  # mamba/mlstm chunk length (state-save granularity)
    pad_vocab_to: int = 256  # pad the LM-head vocab to a multiple (Megatron
    # convention) so logits shard over any TP width; padded slots are
    # masked to -inf and never predicted. 0 disables.
    opt_moments_dtype: str = "float32"  # bf16 halves optimizer HBM (235B arch)
    grad_accum_dtype: str = "float32"  # microbatch grad-accumulation dtype
    kv_cache_dtype: str = "bfloat16"  # "int8" = KIVI-style quantized KV cache
    # (per-token,per-head scales): halves decode-cache HBM vs bf16 — used by
    # the 72B arch whose bf16 cache + params exceed per-chip HBM
    fsdp_inference: bool = False  # FSDP params at serve time (qwen3-moe: the
    # 29 GB model-sharded params force it; dense archs keep TP-only params)

    # --- attention execution -------------------------------------------------
    q_chunk: int = 512  # chunked-attention block sizes (memory-efficient attn)
    kv_chunk: int = 1024
    use_flash_kernel: bool = False  # route attention through the Pallas kernel
    mla_absorbed_decode: bool = True  # latent-space MLA decode (perf iteration)
    causal_skip: bool = False  # dynamic-bound kv loop in prefill attention
    # (skips fully-masked causal blocks; forward-only -> serving paths)
    ssm_tp: bool = True  # tensor-parallel SSM/LSTM channels; False = pure-DP
    # mixers (xlstm: 4 heads x 1024-wide matrix memory makes channel-TP emit
    # per-chunk psums that dominate everything — see §Perf H3)

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.block_pattern is not None:
            assert len(self.block_pattern) == self.n_layers, (
                f"block_pattern len {len(self.block_pattern)} != n_layers {self.n_layers}"
            )
        if self.linear_attn_layers:
            if not all(0 <= i < self.n_layers for i in self.linear_attn_layers):
                raise ValueError(f"{self.name}: linear_attn_layers "
                                 f"{self.linear_attn_layers} outside 0..{self.n_layers - 1}")
            if not (self.linear_n_k_heads and self.linear_n_v_heads
                    and self.linear_k_head_dim and self.linear_v_head_dim
                    and self.linear_conv_kernel):
                raise ValueError(f"{self.name}: linear_attn_layers need the "
                                 f"Gated DeltaNet heads, head dims and conv kernel")
        if self.index_topk or self.index_shared_layers:
            self._check_dsa()

    def _check_dsa(self) -> None:
        if not self.index_topk:
            raise ValueError(f"{self.name}: index_shared_layers need index_topk")
        if not self.use_mla:
            raise ValueError(f"{self.name}: sparse attention selects MLA's "
                             f"keys; it needs use_mla")
        if self.linear_attn_layers:
            raise ValueError(f"{self.name}: sparse attention beside Gated "
                             f"DeltaNet layers is not priced")
        if not (self.index_n_heads and self.index_head_dim):
            raise ValueError(f"{self.name}: index_topk needs the indexer's "
                             f"heads and head dim")
        shared = set(self.index_shared_layers)
        if not all(0 <= i < self.n_layers for i in shared):
            raise ValueError(f"{self.name}: index_shared_layers "
                             f"{self.index_shared_layers} outside 0..{self.n_layers - 1}")
        if 0 in shared:  # else layer 0 runs the indexer, before any shared
            raise ValueError(f"{self.name}: shared layer 0 has no earlier "
                             f"layer running the indexer")

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def is_linear_layer(self, i: int) -> bool:
        """Whether decoder layer ``i`` is Gated DeltaNet (else attention)."""
        return i in self.linear_attn_layers

    @property
    def linear_conv_dim(self) -> int:
        """Channels of a Gated DeltaNet layer's conv: q, k and v."""
        return (2 * self.linear_n_k_heads * self.linear_k_head_dim
                + self.linear_n_v_heads * self.linear_v_head_dim)

    @property
    def linear_state_elems(self) -> int:
        """A Gated DeltaNet layer's held state for one sequence: the
        ``d_k x d_v`` delta-rule state of every value head, plus the last
        ``conv_kernel - 1`` inputs of the conv over q, k and v."""
        hv = self.linear_n_v_heads
        return (hv * self.linear_k_head_dim * self.linear_v_head_dim
                + (self.linear_conv_kernel - 1) * self.linear_conv_dim)

    @property
    def linear_attn_params(self) -> int:
        """One Gated DeltaNet mixer's weights (arXiv:2412.06464): the input
        projection to q, k, v, the output gate z and the per-value-head
        beta and alpha; the depthwise conv over q, k and v; ``A_log`` and
        ``dt_bias``; the gated output norm (one ``d_v`` weight shared by
        the heads); the output projection."""
        d, hv, dv = self.d_model, self.linear_n_v_heads, self.linear_v_head_dim
        return (d * (self.linear_conv_dim + hv * dv + 2 * hv)
                + self.linear_conv_dim * self.linear_conv_kernel + 2 * hv + dv
                + hv * dv * d)

    def is_index_shared(self, i: int) -> bool:
        """Whether decoder layer ``i`` reuses an earlier layer's top-k
        (else, under sparse attention, it runs its own indexer)."""
        return i in self.index_shared_layers

    @property
    def index_params(self) -> int:
        """One lightning indexer's weights (DeepSeek-V3.2-Exp): ``q^I``
        from the query latent, ``k^I`` from the hidden state and its
        LayerNorm (weight and bias), and the per-head weights ``w``."""
        d, hi, di = self.d_model, self.index_n_heads, self.index_head_dim
        return self.q_lora_rank * hi * di + d * di + 2 * di + d * hi

    def is_moe_layer(self, i: int) -> bool:
        """Whether decoder layer ``i`` has an expert FFN (the first
        ``first_k_dense`` layers of a MoE model are dense)."""
        return self.is_moe and i >= self.first_k_dense

    @property
    def vocab_padded(self) -> int:
        if not self.pad_vocab_to:
            return self.vocab
        m = self.pad_vocab_to
        return -(-self.vocab // m) * m

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def pattern(self) -> tuple[str, ...]:
        if self.block_pattern is not None:
            return self.block_pattern
        return ("attn",) * self.n_layers

    @property
    def n_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        d, hd = self.d_model, self.head_dim
        total = self.vocab * d * (1 if self.tie_embeddings else 2)
        for i, kind in enumerate(self.pattern):
            if kind in ("attn",):
                if self.is_linear_layer(i):
                    attn = self.linear_attn_params
                elif self.use_mla:
                    q = d * self.q_lora_rank + self.q_lora_rank * self.n_heads * (
                        self.qk_nope_head_dim + self.qk_rope_head_dim
                    )
                    kv = d * (self.kv_lora_rank + self.qk_rope_head_dim)
                    kv += self.kv_lora_rank * self.n_heads * (
                        self.qk_nope_head_dim + self.v_head_dim
                    )
                    o = self.n_heads * self.v_head_dim * d
                    attn = q + kv + o
                    if self.attn_output_gate:
                        attn += d * self.n_heads * self.v_head_dim
                    if self.index_topk and not self.is_index_shared(i):
                        attn += self.index_params
                else:
                    attn = (self.n_heads + 2 * self.n_kv_heads) * hd * d
                    attn += self.n_heads * hd * d
                if self.is_moe_layer(i):
                    mats = 3 if self.gated_mlp else 2
                    ff = ((self.n_experts + self.n_shared_experts) * mats * d
                          * self.expert_d_ff)
                    ff += d * self.n_experts
                else:
                    ff = (3 if self.gated_mlp else 2) * d * self.d_ff
                total += attn + ff + (4 if self.pre_post_norm else 2) * d
            elif kind == "mamba":
                di = self.d_inner
                total += d * 2 * di + di * self.d_conv + 2 * di * self.ssm_state + di * d + 2 * d
            elif kind in ("mlstm", "slstm"):
                di = self.d_inner
                total += d * 4 * di + di * d + 2 * d
        return total

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        small = dict(
            n_layers=min(self.n_layers, 2 if self.block_pattern is None else len(self._reduced_pattern())),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads > 1 else 1,
            d_ff=128,
            vocab=128,
            head_dim=16,
            moe_group_size=32,
            q_chunk=16,
            kv_chunk=32,
            remat=False,
            dtype="float32",
            train_microbatches=1,
            remat_group=1,
            fsdp=False,
            scan_chunk=16,
        )
        if self.is_moe:
            small.update(n_experts=4, top_k=2)
            if self.moe_d_ff:
                small.update(moe_d_ff=64)
            if self.first_k_dense:
                small.update(first_k_dense=1)
        if self.use_mla:
            small.update(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                         qk_rope_head_dim=8, v_head_dim=16)
        if self.ssm_state:
            small.update(ssm_state=16, ssm_head_dim=16)
        if self.linear_attn_layers:
            # the JAX model runs attention layers only: the small variant
            # keeps Gated DeltaNet widths to match but none of its layers
            small.update(linear_attn_layers=(), linear_n_k_heads=2,
                         linear_n_v_heads=4, linear_k_head_dim=16,
                         linear_v_head_dim=16)
        if self.index_topk:
            # the JAX model runs dense MLA: the small variant drops DSA
            small.update(index_n_heads=0, index_head_dim=0, index_topk=0,
                         index_shared_layers=())
        if self.block_pattern is not None:
            small.update(block_pattern=self._reduced_pattern())
        if self.mrope_sections is not None:
            small.update(mrope_sections=(2, 3, 3))
        small.update(overrides)
        return replace(self, **small)

    def _reduced_pattern(self) -> tuple[str, ...]:
        """First occurrences of each distinct kind, preserving order-of-mix."""
        kinds = list(dict.fromkeys(self.block_pattern))
        return tuple(kinds * 2)[:4] if len(kinds) > 1 else tuple(kinds * 2)
