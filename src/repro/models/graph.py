"""Static layer graphs: per-layer FLOPs / parameter bytes / activation bytes.

These tables are the planner's view of a model (the paper's "measured
per-layer inference and transmission costs"). They are pure-Python shape
math — no JAX — so the planner and benchmarks stay dependency-light; the
real JAX models in ``models/*.py`` align 1:1 with these tables by layer
name, and tests assert the alignment.

Conventions:
  * ``flops`` counts multiply-adds as 2 ops.
  * ``act_bytes`` is the size of the tensor crossing a cut placed
    *after* the node, in deployment dtype (int8 for the TinyML path,
    bf16 for the TPU path) — the paper's Eq. 1 sequential-chain view
    (Table II packet counts confirm only the main tensor is shipped).
    Under IndexShare a cut before a layer that reuses the selection
    also ships the int32 top-k indices (``cut_index_elems``).
  * ``work_bytes`` approximates the peak resident activation set for the
    node (input + output), used for device memory feasibility.
  * ``param_count`` is what the node holds; ``streamed_params`` what a
    step reads of it (``None``: all of it). ``cache_elems`` is the KV or
    latent cache the node holds, ``cache_read_elems`` what a step reads
    of it: the two differ under sparse attention, whose decode step
    reads the top-k latents and not the whole cache. Only the
    DeepSeek-V3 layout (:func:`deepseek_layer_graph`) sets these;
    every other graph reads its parameters whole and counts no cache.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

from repro.core.latency import LayerCost, ModelCostProfile


@dataclass(frozen=True)
class LayerNode:
    name: str
    flops: float
    param_count: int
    out_elems: int  # elements of the output tensor (act bytes = elems * act_dtype)
    work_elems: int  # peak resident activation elements
    streamed_params: float | None = None  # parameters read a step
    cache_elems: int = 0  # resident cache elements
    cache_read_elems: int = 0  # cache elements read a step
    state_elems: int = 0  # resident recurrent state elements (fixed in kv_len)
    state_rw_elems: int = 0  # state elements read and written a step
    cut_index_elems: int = 0  # int32 top-k indices crossing a cut after the node

    @property
    def params_read(self) -> float:
        return self.param_count if self.streamed_params is None else self.streamed_params


@dataclass(frozen=True)
class LayerGraph:
    name: str
    nodes: tuple[LayerNode, ...]
    input_elems: int

    @property
    def num_layers(self) -> int:
        return len(self.nodes)

    @property
    def total_flops(self) -> float:
        return sum(n.flops for n in self.nodes)

    @property
    def total_params(self) -> int:
        return sum(n.param_count for n in self.nodes)

    def node_index(self, name: str) -> int:
        """1-indexed position of a named layer (for paper split points)."""
        for i, n in enumerate(self.nodes):
            if n.name == name:
                return i + 1
        raise KeyError(name)

    def cost_profile(
        self,
        flops_per_s: float,
        act_dtype_bytes: int = 1,
        param_dtype_bytes: int = 1,
    ) -> ModelCostProfile:
        """Convert to a ``ModelCostProfile`` with FLOP-proportional per-layer
        inference times at ``flops_per_s`` (the reference device rate)."""
        layers = [
            LayerCost(
                name=n.name,
                t_infer_s=n.flops / flops_per_s,
                act_bytes=n.out_elems * act_dtype_bytes,
                param_bytes=n.param_count * param_dtype_bytes,
                work_bytes=n.work_elems * act_dtype_bytes,
                flops=n.flops,
            )
            for n in self.nodes
        ]
        return ModelCostProfile(
            name=self.name, layers=tuple(layers), input_bytes=self.input_elems * act_dtype_bytes
        )


# ---------------------------------------------------------------------------
# MobileNet-V2 (paper model 1) — width multiplier, Keras block naming
# ---------------------------------------------------------------------------


def make_divisible(v: float, divisor: int = 8) -> int:
    """TF-slim channel rounding used by MobileNet width multipliers."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


# (expansion t, base channels c, repeats n, first stride s)
_MBV2_GROUPS = [
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
]


def mobilenet_v2_graph(
    width: float = 0.35, image_size: int = 224, num_classes: int = 1000
) -> LayerGraph:
    """MobileNet-V2 flattened to its sequential sub-layer chain.

    Paper split points exist by name: ``block_2_expand`` (56x56x48 @224),
    ``block_15_project`` (7x7x56), ``block_16_project_BN`` (7x7x112)."""
    nodes: list[LayerNode] = []
    h = image_size // 2
    c_in = 3
    c1 = make_divisible(32 * width)
    in_elems = image_size * image_size * 3

    def conv(name, h_out, c_out, c_in, k, in_elems_):
        out = h_out * h_out * c_out
        nodes.append(
            LayerNode(
                name,
                flops=2.0 * h_out * h_out * c_out * c_in * k * k,
                param_count=c_in * c_out * k * k + c_out,
                out_elems=out,
                work_elems=in_elems_ + out,
            )
        )
        return out

    def dwconv(name, h_out, c, k, in_elems_):
        out = h_out * h_out * c
        nodes.append(
            LayerNode(
                name,
                flops=2.0 * h_out * h_out * c * k * k,
                param_count=c * k * k + c,
                out_elems=out,
                work_elems=in_elems_ + out,
            )
        )
        return out

    cur = conv("Conv1", h, c1, 3, 3, in_elems)
    c_in = c1
    block_id = 0
    for t, c_base, n, s in _MBV2_GROUPS:
        c_out = make_divisible(c_base * width)
        for i in range(n):
            stride = s if i == 0 else 1
            h_out = h // stride
            prefix = "expanded_conv" if block_id == 0 else f"block_{block_id}"
            if t != 1:
                cur = conv(f"{prefix}_expand", h, c_in * t, c_in, 1, cur)
                c_mid = c_in * t
            else:
                c_mid = c_in
            cur = dwconv(f"{prefix}_depthwise", h_out, c_mid, 3, cur)
            # project conv + folded BN (+ residual add when stride=1, c_in==c_out)
            cur = conv(f"{prefix}_project_BN", h_out, c_out, c_mid, 1, cur)
            h, c_in = h_out, c_out
            block_id += 1
    cur = conv("Conv_1", h, make_divisible(1280 * max(1.0, width)), c_in, 1, cur)
    c_last = make_divisible(1280 * max(1.0, width))
    # global average pool
    nodes.append(
        LayerNode("global_pool", flops=float(h * h * c_last), param_count=0,
                  out_elems=c_last, work_elems=cur + c_last)
    )
    # classifier
    nodes.append(
        LayerNode("Logits", flops=2.0 * c_last * num_classes,
                  param_count=c_last * num_classes + num_classes,
                  out_elems=num_classes, work_elems=c_last + num_classes)
    )
    return LayerGraph(f"mobilenet_v2_{width}", tuple(nodes), in_elems)


# ---------------------------------------------------------------------------
# ResNet50 (paper model 2)
# ---------------------------------------------------------------------------

_R50_STAGES = [  # (mid channels, out channels, repeats, first stride)
    (64, 256, 3, 1),
    (128, 512, 4, 2),
    (256, 1024, 6, 2),
    (512, 2048, 3, 2),
]


def resnet50_graph(image_size: int = 224, num_classes: int = 1000) -> LayerGraph:
    nodes: list[LayerNode] = []
    in_elems = image_size * image_size * 3

    def conv(name, h_out, c_out, c_in, k, in_elems_):
        out = h_out * h_out * c_out
        nodes.append(
            LayerNode(
                name,
                flops=2.0 * h_out * h_out * c_out * c_in * k * k,
                param_count=c_in * c_out * k * k + c_out,
                out_elems=out,
                work_elems=in_elems_ + out,
            )
        )
        return out

    h = image_size // 2
    cur = conv("conv1", h, 64, 3, 7, in_elems)
    h //= 2  # maxpool
    nodes.append(LayerNode("pool1", flops=float(h * h * 64 * 9), param_count=0,
                           out_elems=h * h * 64, work_elems=cur + h * h * 64))
    cur = h * h * 64
    c_in = 64
    for stage, (c_mid, c_out, n, s) in enumerate(_R50_STAGES, start=2):
        for i in range(n):
            stride = s if i == 0 else 1
            h_out = h // stride
            name = f"conv{stage}_block{i + 1}"
            cur = conv(f"{name}_1", h, c_mid, c_in, 1, cur)
            cur = conv(f"{name}_2", h_out, c_mid, c_mid, 3, cur)
            # 1x1 expand; downsample projection folded into the first block
            proj = c_in * c_out + c_out if i == 0 else 0
            out = h_out * h_out * c_out
            nodes.append(
                LayerNode(
                    f"{name}_3",
                    flops=2.0 * h_out * h_out * c_out * c_mid
                    + (2.0 * h_out * h_out * c_out * c_in if i == 0 else 0.0),
                    param_count=c_mid * c_out + c_out + proj,
                    out_elems=out,
                    work_elems=cur + out,
                )
            )
            cur = out
            h, c_in = h_out, c_out
    nodes.append(LayerNode("avg_pool", flops=float(h * h * c_in), param_count=0,
                           out_elems=c_in, work_elems=cur + c_in))
    nodes.append(LayerNode("fc", flops=2.0 * c_in * num_classes,
                           param_count=c_in * num_classes + num_classes,
                           out_elems=num_classes, work_elems=c_in + num_classes))
    return LayerGraph("resnet50", tuple(nodes), in_elems)


# ---------------------------------------------------------------------------
# Transformer-family graphs (the 10 assigned architectures)
# ---------------------------------------------------------------------------


def _attn_flops(b: int, s: int, d: int, n_heads: int, n_kv: int, head_dim: int,
                kv_len: int | None = None) -> float:
    """QKV + scores + AV + out-proj flops for one attention layer."""
    kv_len = s if kv_len is None else kv_len
    q_proj = 2.0 * b * s * d * (n_heads * head_dim)
    kv_proj = 2.0 * b * s * d * (2 * n_kv * head_dim)
    scores = 2.0 * b * n_heads * s * kv_len * head_dim
    av = 2.0 * b * n_heads * s * kv_len * head_dim
    out = 2.0 * b * s * (n_heads * head_dim) * d
    return q_proj + kv_proj + scores + av + out


def transformer_layer_graph(
    *,
    name: str,
    n_layers: int,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    d_ff: int,
    vocab: int,
    batch: int,
    seq: int,
    head_dim: int | None = None,
    n_experts: int = 0,
    top_k: int = 0,
    gated_mlp: bool = True,
    kv_len: int | None = None,
    tie_embeddings: bool = False,
) -> LayerGraph:
    """Per-block layer graph for a decoder-only LM.

    Each transformer block is one node (split candidates are block
    boundaries — KV caches make intra-block cuts impractical). The
    embedding and LM head are separate nodes. ``kv_len`` models decode
    steps (s=1 query against a long cache)."""
    head_dim = head_dim or d_model // n_heads
    nodes: list[LayerNode] = []
    act = batch * seq * d_model
    in_elems = batch * seq  # token ids

    nodes.append(
        LayerNode("embed", flops=0.0, param_count=vocab * d_model,
                  out_elems=act, work_elems=batch * seq + act)
    )
    mlp_mats = 3 if gated_mlp else 2
    for i in range(n_layers):
        attn = _attn_flops(batch, seq, d_model, n_heads, n_kv_heads, head_dim, kv_len)
        if n_experts > 0:
            ff = 2.0 * batch * seq * d_model * d_ff * mlp_mats * top_k
            router = 2.0 * batch * seq * d_model * n_experts
            ff_params = n_experts * (mlp_mats * d_model * d_ff) + d_model * n_experts
            ff += router
        else:
            ff = 2.0 * batch * seq * d_model * d_ff * mlp_mats
            ff_params = mlp_mats * d_model * d_ff
        attn_params = (n_heads + 2 * n_kv_heads) * head_dim * d_model + n_heads * head_dim * d_model
        nodes.append(
            LayerNode(
                f"block_{i}",
                flops=attn + ff,
                param_count=attn_params + ff_params + 2 * d_model,
                out_elems=act,
                work_elems=2 * act,
            )
        )
    head_params = 0 if tie_embeddings else vocab * d_model
    nodes.append(
        LayerNode("lm_head", flops=2.0 * batch * seq * d_model * vocab,
                  param_count=head_params, out_elems=batch * seq * vocab,
                  work_elems=act + batch * seq * vocab)
    )
    return LayerGraph(name, tuple(nodes), in_elems)


def arch_layer_graph(cfg, batch: int, seq: int, kv_len: int | None = None,
                     act_dtype_bytes: int = 2) -> LayerGraph:
    """LayerGraph for any assigned :class:`ModelConfig` — walks the block
    pattern with per-kind FLOP/param/activation formulas. Used by the
    analytic roofline terms and by :func:`plan_pipeline` on real archs.
    A config with a dense prefix, shared experts, MTP modules, Gated
    DeltaNet layers or sparse attention takes the DeepSeek-V3 layout
    (:func:`deepseek_layer_graph`)."""
    if (cfg.first_k_dense or cfg.n_shared_experts or cfg.n_mtp_modules
            or cfg.linear_attn_layers or cfg.index_topk):
        return deepseek_layer_graph(cfg, batch, seq, kv_len)
    d = cfg.d_model
    nodes: list[LayerNode] = []
    act = batch * seq * d
    embed_params = cfg.vocab * d * max(1, cfg.n_codebooks)
    nodes.append(LayerNode("embed", flops=0.0, param_count=embed_params,
                           out_elems=act, work_elems=2 * act))
    for i, kind in enumerate(cfg.pattern):
        if kind == "attn":
            if cfg.use_mla:
                dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
                H = cfg.n_heads
                kv = seq if kv_len is None else kv_len
                f = 2.0 * batch * seq * (
                    d * cfg.q_lora_rank + cfg.q_lora_rank * H * (dn + dr)
                    + d * (cfg.kv_lora_rank + dr))
                # absorbed-score decode path: latent-space attention
                f += 2.0 * batch * H * seq * kv * (cfg.kv_lora_rank + dr) * 2
                f += 2.0 * batch * seq * H * dv * d
                p = _mla_weights(cfg)
            else:
                f = _attn_flops(batch, seq, d, cfg.n_heads, cfg.n_kv_heads,
                                cfg.head_dim, kv_len)
                p = ((cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim * d
                     + cfg.n_heads * cfg.head_dim * d)
            if cfg.is_moe:
                mats = 3 if cfg.gated_mlp else 2
                f += 2.0 * batch * seq * d * cfg.d_ff * mats * cfg.top_k
                f += 2.0 * batch * seq * d * cfg.n_experts
                p += cfg.n_experts * mats * d * cfg.d_ff + d * cfg.n_experts
            elif cfg.d_ff:
                mats = 3 if cfg.gated_mlp else 2
                f += 2.0 * batch * seq * d * cfg.d_ff * mats
                p += mats * d * cfg.d_ff
            nodes.append(LayerNode(f"block_{i}_attn", flops=f, param_count=p + 2 * d,
                                   out_elems=act, work_elems=2 * act))
        elif kind == "mamba":
            di, ds = cfg.d_inner, cfg.ssm_state
            nh = di // cfg.ssm_head_dim
            f = 2.0 * batch * seq * (d * (2 * di + 2 * ds + nh)  # in_proj
                                     + (di + 2 * ds) * cfg.d_conv  # conv
                                     + 2 * di * ds  # scan state update + out
                                     + di * d)  # out_proj
            p = (d * (2 * di + 2 * ds + nh) + (di + 2 * ds) * cfg.d_conv
                 + 2 * nh + nh + di * d)
            nodes.append(LayerNode(f"block_{i}_mamba", flops=f, param_count=p + d,
                                   out_elems=act, work_elems=2 * act))
        elif kind in ("mlstm", "slstm"):
            di = cfg.d_inner
            f = 2.0 * batch * seq * (d * (3 * di + 2 * cfg.n_heads) + di * d)
            if kind == "mlstm":
                ph = di // cfg.n_heads
                # chunk-parallel matrix-memory terms
                f += 2.0 * batch * seq * cfg.n_heads * ph * ph * 2
            else:
                ph = di // cfg.n_heads
                f += 2.0 * batch * seq * cfg.n_heads * ph * 4 * ph
            p = d * (4 * di if kind == "slstm" else 3 * di + 2 * cfg.n_heads) + di * d
            nodes.append(LayerNode(f"block_{i}_{kind}", flops=f, param_count=p + d,
                                   out_elems=act, work_elems=2 * act))
    head_p = 0 if cfg.tie_embeddings else cfg.vocab_padded * d * max(1, cfg.n_codebooks)
    nodes.append(LayerNode(
        "lm_head",
        flops=2.0 * batch * seq * d * cfg.vocab_padded * max(1, cfg.n_codebooks),
        param_count=head_p,
        out_elems=batch * seq * cfg.vocab_padded,
        work_elems=act + batch * seq * cfg.vocab_padded))
    return LayerGraph(cfg.name, tuple(nodes), batch * seq)


def experts_touched(n_experts: int, top_k: int, tokens: int) -> float:
    """Expected number of distinct experts ``tokens`` tokens route to,
    each picking ``top_k`` of ``n_experts`` uniformly:
    E * (1 - (1 - k/E)^T)."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** tokens)


def _mla_weights(cfg) -> int:
    """One MLA block's projection matrices: W_DQ, W_UQ/W_QR; W_DKV/W_KR,
    W_UK/W_UV; W_O."""
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    return (d * qr + qr * H * (dn + dr)
            + d * (kr + dr) + kr * H * (dn + dv)
            + H * dv * d)


def _mla_params(cfg) -> int:
    """One MLA block's weights: the projections plus the norms of the
    query and key-value latents."""
    return _mla_weights(cfg) + cfg.q_lora_rank + cfg.kv_lora_rank


def _mla_flops(cfg, batch: int, seq: int, kv: int) -> float:
    """One MLA block, latent-space (absorbed) form: projections, W_UK
    folded into the query, scores over the latent plus rope key, the
    latent-weighted sum, W_UV and W_O. Every (query, key) pair counts:
    no causal halving."""
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    T = batch * seq
    return (2.0 * T * d * qr + 2.0 * T * qr * H * dn + 2.0 * T * qr * H * dr
            + 2.0 * T * d * kr + 2.0 * T * d * dr  # down/up projections
            + 2.0 * T * H * dn * kr  # W_UK folded into the query
            + 2.0 * batch * H * seq * kv * (kr + dr)  # scores
            + 2.0 * batch * H * seq * kv * kr  # latent-weighted sum
            + 2.0 * T * H * kr * dv + 2.0 * T * H * dv * d)  # W_UV, W_O


# Gated DeltaNet's chunk length in the chunkwise (prefill) form
GDN_CHUNK = 64


def _gdn_flops(cfg, batch: int, seq: int) -> float:
    """One Gated DeltaNet mixer (arXiv:2412.06464) over ``batch x seq``
    tokens: the input projection (q, k, v, z, beta, alpha), the depthwise
    conv over q, k and v, the delta rule and the output projection.

    A one-token step runs the recurrent form: per token and value head,
    the retrieval ``S^T k``, the rank-1 update and the readout ``S q``
    (three ``d_k x d_v`` products) and the decay of ``S`` (one). Longer
    steps run the chunkwise form (arXiv:2406.06484 section 3) over
    ``ceil(seq / GDN_CHUNK)`` chunks a sequence, each per value head:
    ``A = beta K K^T`` (strictly lower), W and U by forward substitution
    through ``I + A`` (half a square product each), the masked ``Q K^T``
    and its product with ``U - W S``, the three chunk-state products
    ``W S``, ``Q S`` and ``K^T (U - W S)``, and the chunk's decay of
    ``S``. Element-wise gates and norms are not counted."""
    d, T = cfg.d_model, batch * seq
    hv, dk, dv = cfg.linear_n_v_heads, cfg.linear_k_head_dim, cfg.linear_v_head_dim
    conv = cfg.linear_conv_dim  # q, k, v
    flops = (2.0 * T * d * (conv + hv * dv + 2 * hv)  # in_proj: + z, beta, alpha
             + 2.0 * T * conv * cfg.linear_conv_kernel  # depthwise conv
             + 2.0 * T * hv * dv * d)  # out_proj
    if seq == 1:
        return flops + 7.0 * T * hv * dk * dv
    C = GDN_CHUNK
    chunk = (2.0 * C * C * dk + 1.0 * C * C * (dk + dv)
             + 2.0 * C * C * dk + 2.0 * C * C * dv
             + 6.0 * C * dk * dv + 1.0 * dk * dv)
    return flops + batch * math.ceil(seq / C) * hv * chunk


def _dsa_flops(cfg, batch: int, seq: int, kv: int) -> float:
    """The lightning indexer of a layer that runs it (DeepSeek-V3.2-Exp):
    the ``q^I``, ``k^I`` and ``w`` projections, then
    ``I_ts = sum_j w_tj ReLU(q^I_tj . k^I_s)`` over every (query, key)
    pair, as MLA counts them. Top-k selection is not counted."""
    d, hi, di = cfg.d_model, cfg.index_n_heads, cfg.index_head_dim
    T = batch * seq
    return (2.0 * T * (cfg.q_lora_rank * hi * di + d * di + d * hi)
            + 2.0 * batch * seq * kv * hi * (di + 1))


def _deepseek_block(cfg, i: int | None, batch: int, seq: int, kv: int,
                    decode: bool):
    """(flops, resident, streamed, cache, cache_read, state) of one
    decoder block: MLA (with its output gate where the config has one,
    over the indexer's top-k under sparse attention) or, for a layer
    ``i`` in ``linear_attn_layers``, Gated DeltaNet; then a dense SwiGLU
    (layer ``i`` < ``first_k_dense``) or the routed experts, the shared
    experts and the router. ``i`` None is an MTP module's block: MLA
    (running its own indexer under sparse attention), and MoE unless
    ``mtp_dense``. ``cache`` is the MLA latent cache (and the index
    keys), ``cache_read`` what a step reads of it (``decode``: a step
    continuing ``kv`` cached positions), ``state`` the Gated DeltaNet
    state (fixed in ``kv``)."""
    d, T = cfg.d_model, batch * seq
    mats = 3 if cfg.gated_mlp else 2
    norms = (4 if cfg.pre_post_norm else 2) * d  # attention and FFN norms
    cache = cache_read = state = 0
    if i is not None and cfg.is_linear_layer(i):
        flops = _gdn_flops(cfg, batch, seq)
        attn = cfg.linear_attn_params + norms
        state = batch * cfg.linear_state_elems
    else:
        latent = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        attended = min(kv, cfg.index_topk) if cfg.index_topk else kv
        flops = _mla_flops(cfg, batch, seq, attended)
        attn = _mla_params(cfg) + norms
        if cfg.attn_output_gate:  # sigmoid(x W_G) on every head's output
            gate = d * cfg.n_heads * cfg.v_head_dim
            flops += 2.0 * T * gate
            attn += gate
        cache = batch * kv * latent
        cache_read = batch * attended * latent if decode else cache
        if cfg.index_topk and (i is None or not cfg.is_index_shared(i)):
            keys = batch * kv * cfg.index_head_dim  # k^I of every cached token
            flops += _dsa_flops(cfg, batch, seq, kv)
            attn += cfg.index_params
            cache += keys
            cache_read += keys
    dense = cfg.mtp_dense if i is None else not cfg.is_moe_layer(i)
    if dense:
        ffn = mats * d * cfg.d_ff
        flops += 2.0 * T * ffn
        params, streamed = attn + ffn, float(attn + ffn)
    else:
        E, Es = cfg.n_experts, cfg.n_shared_experts
        expert = mats * d * cfg.expert_d_ff
        router = d * E + E  # gate and its score-correction bias
        flops += 2.0 * T * cfg.top_k * expert + 2.0 * T * Es * expert
        flops += 2.0 * T * d * E
        params = attn + E * expert + Es * expert + router
        streamed = (attn + experts_touched(E, cfg.top_k, T) * expert
                    + Es * expert + router)
    return flops, params, streamed, cache, cache_read, state


def deepseek_layer_graph(cfg, batch: int, seq: int,
                         kv_len: int | None = None) -> LayerGraph:
    """DeepSeek-V3's layout as pipeline-stage candidates (arXiv:2412.19437):
    ``embed``, ``layer_0`` ... ``layer_{n-1}``, ``head``.

    Layers below ``first_k_dense`` have a dense SwiGLU of ``d_ff``; the
    rest have ``n_experts`` routed experts of ``moe_d_ff`` (``top_k`` a
    token), ``n_shared_experts`` shared ones and the router. Their mixer
    is MLA, or Gated DeltaNet for the layers in ``linear_attn_layers``
    (GigaChat3.5's hybrid). ``head`` holds the final norm, the output
    head and the MTP modules (each: two norms, the ``2d -> d``
    projection, one MLA block with the MoE FFN, or the dense one where
    ``mtp_dense``, the shared head's norm and the shared head applied
    again); MTP sits on ``head`` because it reads both the last hidden
    state and the next token's embedding, so a cut between them would
    ship two tensors. The embedding copy MTP reads is resident there.

    Per block, with ``T = batch x seq``:

    * MLA (:func:`_mla_flops`, :func:`_mla_params`): projections, latent
      scores and sums over every (query, key) pair; with
      ``attn_output_gate`` a ``d -> heads x v_head_dim`` gate adds
      ``2 T d heads v_head_dim`` FLOPs and its weights. It holds and
      reads a latent cache of ``kv_lora_rank + qk_rope_head_dim`` a token
      a sequence, in the activation dtype.
    * DeepSeek Sparse Attention (``index_topk``; DeepSeek-V3.2-Exp): MLA
      attends to ``min(kv, index_topk)`` keys a query. A layer that runs
      the lightning indexer (:func:`_dsa_flops`, weights
      ``ModelConfig.index_params``; the MTP block does) scores every
      cached token and holds and reads ``index_head_dim`` index-key
      values a token besides the latent. A decode step reads the top-k
      latents, whatever the cache holds; a prefill reads the held cache
      once. A layer in ``index_shared_layers`` reuses the nearest
      earlier selection, so a cut just before it ships the
      ``batch x seq x min(kv, index_topk)`` int32 indices
      (``cut_index_elems`` of the node before the cut) besides the
      hidden state.
    * Gated DeltaNet (:func:`_gdn_flops`; weights
      ``ModelConfig.linear_attn_params``): it holds
      ``ModelConfig.linear_state_elems`` a sequence (the delta-rule state
      and the conv's last inputs), whatever ``kv_len``, in
      ``linear_state_dtype``. A step that continues a sequence
      (``kv_len`` given) reads and writes it; a prefill writes it once.
    * Norms: two a block, four with ``pre_post_norm``.

    A step (``kv_len`` cached positions at decode; ``seq`` at prefill)
    reads the experts it touches (:func:`experts_touched`, uniform
    routing), the embedding rows it looks up, and every other weight
    once; the output head is read once per application.

    Only MLA attention is priced here: a config without ``use_mla`` is
    refused rather than given attention with no weights or cache."""
    if not cfg.use_mla:
        raise ValueError(
            f"{cfg.name}: the dense-prefix / shared-expert / MTP layout is "
            f"priced for MLA attention only (use_mla is False)")
    d, V = cfg.d_model, cfg.vocab
    T = batch * seq
    kv = seq if kv_len is None else kv_len
    rw = 1 if kv_len is None else 2  # state writes, plus reads at decode
    act = T * d
    rows = experts_touched(V, 1, T) * d  # embedding rows a lookup reads
    nodes = [LayerNode("embed", flops=0.0, param_count=V * d, out_elems=act,
                       work_elems=2 * act, streamed_params=rows)]
    decode = kv_len is not None
    picked = T * min(kv, cfg.index_topk)  # top-k indices a step selects
    for i in range(cfg.n_layers):
        f, p, s, c, cr, st = _deepseek_block(cfg, i, batch, seq, kv, decode)
        ships = cfg.index_topk and cfg.is_index_shared(i + 1)
        nodes.append(LayerNode(f"layer_{i}", flops=f, param_count=p,
                               out_elems=act, work_elems=2 * act,
                               streamed_params=s, cache_elems=c,
                               cache_read_elems=cr, state_elems=st,
                               state_rw_elems=rw * st,
                               cut_index_elems=picked if ships else 0))
    head_w = V * d
    flops = 2.0 * T * d * V
    params = d + head_w
    streamed = float(d + head_w)
    cache = cache_read = 0
    for _ in range(cfg.n_mtp_modules):
        f, p, s, c, cr, _ = _deepseek_block(cfg, None, batch, seq, kv, decode)
        own = 2 * d + 2 * d * d + d  # enorm, hnorm, eh_proj, head norm
        flops += 2.0 * T * 2 * d * d + f + 2.0 * T * d * V
        params += own + p + V * d  # + the embedding copy
        streamed += own + s + rows + head_w
        cache += c
        cache_read += cr
    nodes.append(LayerNode("head", flops=flops, param_count=params,
                           out_elems=T * V, work_elems=act + T * V,
                           streamed_params=streamed, cache_elems=cache,
                           cache_read_elems=cache_read))
    return LayerGraph(cfg.name, tuple(nodes), T)


def ssm_layer_graph(
    *,
    name: str,
    n_layers: int,
    d_model: int,
    d_state: int,
    vocab: int,
    batch: int,
    seq: int,
    expand: int = 2,
    conv_dim: int = 4,
) -> LayerGraph:
    """Mamba2-style SSM block chain (used for zamba2 / xlstm planning)."""
    d_inner = expand * d_model
    nodes: list[LayerNode] = []
    act = batch * seq * d_model
    nodes.append(LayerNode("embed", flops=0.0, param_count=vocab * d_model,
                           out_elems=act, work_elems=act))
    for i in range(n_layers):
        in_proj = 2.0 * batch * seq * d_model * (2 * d_inner)
        conv = 2.0 * batch * seq * d_inner * conv_dim
        scan = 2.0 * batch * seq * d_inner * d_state * 2
        out_proj = 2.0 * batch * seq * d_inner * d_model
        params = d_model * 2 * d_inner + d_inner * conv_dim + d_inner * d_state * 2 + d_inner * d_model
        nodes.append(LayerNode(f"ssm_block_{i}", flops=in_proj + conv + scan + out_proj,
                               param_count=params + 2 * d_model, out_elems=act, work_elems=2 * act))
    nodes.append(LayerNode("lm_head", flops=2.0 * batch * seq * d_model * vocab,
                           param_count=vocab * d_model, out_elems=batch * seq * vocab,
                           work_elems=act + batch * seq * vocab))
    return LayerGraph(name, tuple(nodes), batch * seq)
