"""Plain float64 reference of a hybrid MLA + Gated DeltaNet model
(GigaChat3.5-432B-A28B) planned as pipeline stages on TPU v5e chip
groups, from a deployment file (``bench/configs/<name>.json``: the
published ``config.json`` keys at its top level, the stage device, the
links and the state dtype beside them), with no code of the system under
test.

The layer table is written from the papers and the published keys:

* MLA (DeepSeek-V3, arXiv:2412.19437 eqs. 1-11) in the layers of
  ``full_attention_layers``: W^DQ (d -> q_lora) and its RMSNorm, W^UQ
  and W^QR; W^DKV (d -> kv_lora) and its RMSNorm, W^KR; W^UK and W^UV;
  W^O. ``gated_attention``: a gate W^G (d -> heads x v_head) whose
  sigmoid multiplies every head's output before W^O. Attention runs in
  the latent space over every (query, key) pair; each sequence holds
  and reads kv_lora + qk_rope cached values a token, in the activation
  dtype.
* Gated DeltaNet (arXiv:2412.06464) in every other layer, with
  ``linear_num_key_heads`` key heads of ``linear_key_head_dim`` and
  ``linear_num_value_heads`` value heads of ``linear_value_head_dim``:
  an input projection to q, k (key heads), v, z (value heads), beta
  and alpha (one each a value head); a depthwise causal conv of
  ``linear_conv_kernel`` over q, k and v (no bias); A_log and dt_bias;
  a gated output norm (one value-head-wide weight shared by the heads);
  the output projection. The recurrent state is one d_k x d_v matrix a
  value head, plus the conv's last kernel - 1 inputs, fixed in the KV
  length and held in ``state_dtype``. A one-token step runs the delta
  rule's recurrence, per token and value head: S^T k, the rank-1 update
  beta (v - S^T k) k^T, the readout S q (2 d_k d_v FLOPs each) and the
  decay alpha S (d_k d_v). A longer step runs the chunkwise form
  (arXiv:2406.06484 section 3) over chunks of CHUNK tokens, each per
  value head: A = beta K K^T (2 C^2 d_k), W and U by forward
  substitution through I + A (C^2 d_k and C^2 d_v), the masked Q K^T
  (2 C^2 d_k) and its product with U - W S (2 C^2 d_v), the chunk-state
  products W S, Q S and K^T (U - W S) (2 C d_k d_v each) and the chunk's
  decay of S (d_k d_v). Element-wise gates and norms are not counted. A
  step continuing a sequence (decode) reads and writes the state; a
  prefill writes it once.
* FFN: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers, then ``n_routed_experts`` routed and
  ``n_shared_experts`` shared experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` routed a token, with d x E router weights and
  E selection biases.
* ``layernorm_type`` pre_post: four d-wide norms a block (before and
  after each sublayer); two otherwise.
* MTP: per module two norms, the 2d -> d projection, one MLA block with
  a dense FFN of ``intermediate_size`` (``nextn_is_sparse`` false), the
  shared head's norm and the shared head applied again; folded into the
  ``head`` node with a resident copy of the embedding, as in
  ``bench/reference/pipeline.py``.

Routing, the layer times (one chip's roofline divided by the stage's
chips), the stage cost, the memory check and the bottleneck DP are those
of ``bench/reference/pipeline.py``, whose deployment class this one
extends: its priced table states the recurrent state in activation-dtype
units (state bytes over activation bytes an element), so the held bytes
and the bytes a step moves carry the state in its own dtype.
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference.pipeline import PipelineDeployment

CHUNK = 64


def experts_touched(E: int, k: int, T: int) -> float:
    return E * (1.0 - (1.0 - k / E) ** T)


def _norms(hp: dict) -> int:
    return (4 if hp.get("layernorm_type") == "pre_post" else 2) * hp["hidden_size"]


def _mla(hp: dict, B: int, S: int, K: int) -> tuple[float, int, int]:
    """(flops, weights, cache) of one MLA mixer."""
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    qr, kr = hp["q_lora_rank"], hp["kv_lora_rank"]
    dn, dr, dv = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"], hp["v_head_dim"]
    T = B * S
    weights = (d * qr + qr            # W^DQ, its RMSNorm
               + qr * H * dn + qr * H * dr  # W^UQ, W^QR
               + d * kr + d * dr + kr  # W^DKV, W^KR, its RMSNorm
               + kr * H * dn + kr * H * dv  # W^UK, W^UV
               + H * dv * d)          # W^O
    flops = (2.0 * T * d * qr + 2.0 * T * qr * H * dn + 2.0 * T * qr * H * dr
             + 2.0 * T * d * kr + 2.0 * T * d * dr
             + 2.0 * T * H * dn * kr          # W^UK folded into the query
             + 2.0 * B * H * S * K * (kr + dr)  # scores
             + 2.0 * B * H * S * K * kr       # weighted sum of latents
             + 2.0 * T * H * kr * dv + 2.0 * T * H * dv * d)  # W^UV, W^O
    if hp.get("gated_attention"):
        weights += d * H * dv
        flops += 2.0 * T * d * H * dv
    return flops, weights, B * K * (kr + dr)


def _gdn(hp: dict, B: int, S: int) -> tuple[float, int, int]:
    """(flops, weights, state a batch) of one Gated DeltaNet mixer."""
    d = hp["hidden_size"]
    hk, hv = hp["linear_num_key_heads"], hp["linear_num_value_heads"]
    dk, dv = hp["linear_key_head_dim"], hp["linear_value_head_dim"]
    kc = hp["linear_conv_kernel_dim"]
    T = B * S
    qkv = hk * dk + hk * dk + hv * dv
    proj = qkv + hv * dv + hv + hv  # q, k, v, z, beta, alpha
    weights = (d * proj + qkv * kc + hv + hv  # in_proj, conv, A_log, dt_bias
               + dv + hv * dv * d)            # output norm, out_proj
    flops = 2.0 * T * d * proj + 2.0 * T * qkv * kc + 2.0 * T * hv * dv * d
    if S == 1:
        flops += T * hv * (2.0 * dk * dv * 3 + dk * dv)
    else:
        C = CHUNK
        per_chunk = (2.0 * C * C * dk           # A
                     + C * C * dk + C * C * dv  # W, U
                     + 2.0 * C * C * dk + 2.0 * C * C * dv  # Q K^T, (.)(U - W S)
                     + 3 * 2.0 * C * dk * dv    # W S, Q S, K^T (U - W S)
                     + dk * dv)                 # decay
        flops += B * math.ceil(S / C) * hv * per_chunk
    state = B * (hv * dk * dv + (kc - 1) * qkv)
    return flops, weights, state


def _block(hp: dict, layer: int | None, B: int, S: int, K: int) -> dict:
    """Decoder layer ``layer``, or an MTP module's block (``None``)."""
    d, T = hp["hidden_size"], B * S
    linear = layer is not None and layer not in hp["full_attention_layers"]
    if linear:
        flops, attn, state = _gdn(hp, B, S)
        cache = 0
    else:
        flops, attn, cache = _mla(hp, B, S, K)
        state = 0
    attn += _norms(hp)
    if layer is None:
        dense = not hp["nextn_is_sparse"]
    else:
        dense = layer < hp["first_k_dense_replace"]
    if dense:
        ffn = 3 * d * hp["intermediate_size"]
        flops += 2.0 * T * ffn
        resident, streamed = attn + ffn, float(attn + ffn)
    else:
        E, k = hp["n_routed_experts"], hp["num_experts_per_tok"]
        Es = hp["n_shared_experts"]
        expert = 3 * d * hp["moe_intermediate_size"]
        router = d * E + E
        flops += 2.0 * T * k * expert + 2.0 * T * Es * expert + 2.0 * T * d * E
        resident = attn + E * expert + Es * expert + router
        streamed = attn + experts_touched(E, k, T) * expert + Es * expert + router
    return {"flops": flops, "resident": resident, "streamed": streamed,
            "cache": cache, "state": state}


def layer_table(hp: dict, batch: int, seq: int, kv_len: int | None = None):
    """One row per stage candidate (``embed``, ``layer_i``, ``head``), in
    elements: ``cache``/``cache_read`` the latent cache held and read,
    ``state``/``state_rw`` the recurrent state held and read plus
    written a step."""
    d, V = hp["hidden_size"], hp["vocab_size"]
    T = batch * seq
    K = seq if kv_len is None else kv_len
    rw = 1 if kv_len is None else 2
    act = T * d
    rows_read = experts_touched(V, 1, T) * d
    table = [dict(name="embed", flops=0.0, resident=V * d, streamed=rows_read,
                  cache=0, cache_read=0, state=0, state_rw=0, out=act,
                  work=2 * act)]
    for i in range(hp["num_hidden_layers"]):
        b = _block(hp, i, batch, seq, K)
        table.append(dict(name=f"layer_{i}", flops=b["flops"],
                          resident=b["resident"], streamed=b["streamed"],
                          cache=b["cache"], cache_read=b["cache"],
                          state=b["state"], state_rw=rw * b["state"],
                          out=act, work=2 * act))
    head = V * d
    flops = 2.0 * T * d * V
    resident, streamed, cache = d + head, float(d + head), 0
    for _ in range(hp["num_nextn_predict_layers"]):
        b = _block(hp, None, batch, seq, K)
        own = d + d + 2 * d * d + d  # two RMSNorms, M_k, the head's RMSNorm
        flops += 2.0 * T * 2 * d * d + b["flops"] + 2.0 * T * d * V
        resident += own + b["resident"] + V * d  # + the embedding copy
        streamed += own + b["streamed"] + rows_read + head
        cache += b["cache"]
    table.append(dict(name="head", flops=flops, resident=resident,
                      streamed=streamed, cache=cache, cache_read=cache,
                      state=0, state_rw=0, out=T * V, work=act + T * V))
    return table


class HybridDeployment(PipelineDeployment):
    """``PipelineDeployment`` with the hybrid layer table."""

    def table(self, shape: tuple):
        """``shape`` = (kind, seq_len, batch); cached. The state goes
        into the priced ``cache`` and ``cache_read`` in activation-dtype
        units."""
        t = self._tables.get(shape)
        if t is None:
            kind, seq_len, batch = shape
            if kind == "decode":
                rows = layer_table(self.hp, batch, 1, seq_len)
            else:
                rows = layer_table(self.hp, batch, seq_len)
            per = np.dtype(self.hp["state_dtype"]).itemsize / self.a_bytes
            t = [dict(r, cache=r["cache"] + r["state"] * per,
                      cache_read=r["cache_read"] + r["state_rw"] * per)
                 for r in rows]
            self._tables[shape] = t
        return t
