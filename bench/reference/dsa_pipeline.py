"""Plain float64 reference of an MLA + MoE model with DeepSeek Sparse
Attention and IndexShare (GLM-5.2) planned as pipeline stages on TPU v5e
chip groups, from a deployment file (``bench/configs/<name>.json``: the
published ``config.json`` keys at its top level, the stage device and
the links beside them), with no code of the system under test.

The layer table is written from the reports and the published keys:

* MLA (DeepSeek-V3, arXiv:2412.19437 eqs. 1-11), as in
  ``bench/reference/pipeline.py``: W^DQ (d -> q_lora) and its RMSNorm,
  W^UQ and W^QR; W^DKV (d -> kv_lora) and its RMSNorm, W^KR; W^UK and
  W^UV; W^O. Attention runs in the latent space. Each sequence holds
  kv_lora + qk_rope cached latent values a token, in the activation
  dtype.
* DeepSeek Sparse Attention (DeepSeek-V3.2-Exp report, section 2.1):
  MLA attends, for each query, to the ``index_topk`` cached tokens the
  lightning indexer selects, or to all of them where fewer are cached.
  The indexer has ``index_n_heads`` heads of ``index_head_dim``: q^I
  from the query latent (q_lora -> heads x dim), one key k^I a token
  from the hidden state (d -> dim, with a LayerNorm: weight and bias)
  and per-head weights w from the hidden state (d -> heads). Its score
  I_ts = sum_j w_tj ReLU(q^I_tj . k^I_s) costs 2 heads (dim + 1) FLOPs
  a (query, key) pair, every pair counted as MLA counts them; the top-k
  selection is not counted. The index keys are cached: dim values a
  token, in the activation dtype.
* IndexShare (``indexer_types``): a ``full`` layer runs its indexer and
  holds its index keys; a ``shared`` layer has neither and attends to
  the selection of the nearest earlier ``full`` layer. A decode step
  reads min(K, topk) latents a sequence, plus, in a ``full`` layer,
  every cached index key; a prefill reads the held cache once. A stage
  that starts at a ``shared`` layer needs the selected indices, B x S x
  min(K, topk) int32 values, shipped beside the hidden state.
* FFN: SwiGLU of ``intermediate_size`` in the ``dense`` layers of
  ``mlp_layer_types``, else ``n_routed_experts`` routed and
  ``n_shared_experts`` shared experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` routed a token, with d x E router weights and
  E selection biases. Two RMSNorms a block.
* MTP: per module two RMSNorms, the 2d -> d projection, one MLA block
  that runs its own indexer, with the MoE FFN, the shared head's norm
  and the shared head applied again; folded into the ``head`` node with
  a resident copy of the embedding, as in ``bench/reference/pipeline.py``.

Routing, the layer times, the stage cost, the memory check and the
bottleneck DP are those of ``bench/reference/pipeline.py``, whose
deployment class this one extends with its own table and a shipping
time that carries the indices.
"""

from __future__ import annotations

import math

import numpy as np

from bench.reference.pipeline import PipelineDeployment

INDEX_BYTES = 4  # int32


def experts_touched(E: int, k: int, T: int) -> float:
    return E * (1.0 - (1.0 - k / E) ** T)


def _mla(hp: dict, B: int, S: int, K: int) -> tuple[float, int]:
    """(flops, weights) of one MLA mixer attending to K keys a query."""
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
    return flops, weights


def _indexer(hp: dict, B: int, S: int, K: int) -> tuple[float, int]:
    """(flops, weights) of one lightning indexer scoring K keys a query."""
    d, qr = hp["hidden_size"], hp["q_lora_rank"]
    hi, di = hp["index_n_heads"], hp["index_head_dim"]
    T = B * S
    weights = (qr * hi * di   # q^I
               + d * di       # k^I
               + di + di      # its LayerNorm: weight and bias
               + d * hi)      # w
    flops = (2.0 * T * qr * hi * di + 2.0 * T * d * di + 2.0 * T * d * hi
             + 2.0 * B * S * K * hi * (di + 1))  # w-weighted ReLU scores
    return flops, weights


def _block(hp: dict, layer: int | None, B: int, S: int, K: int,
           decode: bool) -> dict:
    """Decoder layer ``layer``, or an MTP module's block (``None``)."""
    d, T = hp["hidden_size"], B * S
    topk = min(K, hp["index_topk"])
    latent = hp["kv_lora_rank"] + hp["qk_rope_head_dim"]
    flops, attn = _mla(hp, B, S, topk)
    attn += 2 * d  # the block's two RMSNorms
    cache = B * K * latent
    cache_read = B * topk * latent if decode else cache
    if layer is None or hp["indexer_types"][layer] == "full":
        f, w = _indexer(hp, B, S, K)
        keys = B * K * hp["index_head_dim"]
        flops += f
        attn += w
        cache += keys
        cache_read += keys
    dense = layer is not None and hp["mlp_layer_types"][layer] == "dense"
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
            "cache": cache, "cache_read": cache_read}


def layer_table(hp: dict, batch: int, seq: int, kv_len: int | None = None):
    """One row per stage candidate (``embed``, ``layer_i``, ``head``), in
    elements: ``cache``/``cache_read`` the latent cache and index keys
    held and read, ``index_out`` the int32 indices a cut after the row
    ships (before a ``shared`` layer)."""
    d, V = hp["hidden_size"], hp["vocab_size"]
    n = hp["num_hidden_layers"]
    T = batch * seq
    K = seq if kv_len is None else kv_len
    decode = kv_len is not None
    act = T * d
    picked = T * min(K, hp["index_topk"])
    rows_read = experts_touched(V, 1, T) * d
    table = [dict(name="embed", flops=0.0, resident=V * d, streamed=rows_read,
                  cache=0, cache_read=0, index_out=0, out=act, work=2 * act)]
    for i in range(n):
        b = _block(hp, i, batch, seq, K, decode)
        shared_next = i + 1 < n and hp["indexer_types"][i + 1] == "shared"
        table.append(dict(name=f"layer_{i}", flops=b["flops"],
                          resident=b["resident"], streamed=b["streamed"],
                          cache=b["cache"], cache_read=b["cache_read"],
                          index_out=picked if shared_next else 0,
                          out=act, work=2 * act))
    head = V * d
    flops = 2.0 * T * d * V
    resident, streamed, cache, cache_read = d + head, float(d + head), 0, 0
    for _ in range(hp["num_nextn_predict_layers"]):
        b = _block(hp, None, batch, seq, K, decode)
        own = d + d + 2 * d * d + d  # two RMSNorms, M_k, the head's RMSNorm
        flops += 2.0 * T * 2 * d * d + b["flops"] + 2.0 * T * d * V
        resident += own + b["resident"] + V * d  # + the embedding copy
        streamed += own + b["streamed"] + rows_read + head
        cache += b["cache"]
        cache_read += b["cache_read"]
    table.append(dict(name="head", flops=flops, resident=resident,
                      streamed=streamed, cache=cache, cache_read=cache_read,
                      index_out=0, out=T * V, work=act + T * V))
    return table


class DSADeployment(PipelineDeployment):
    """``PipelineDeployment`` with the DSA layer table, whose cuts before
    a ``shared`` layer ship the indices with the hidden state."""

    def table(self, shape: tuple):
        """``shape`` = (kind, seq_len, batch); cached."""
        t = self._tables.get(shape)
        if t is None:
            kind, seq_len, batch = shape
            if kind == "decode":
                t = layer_table(self.hp, batch, 1, seq_len)
            else:
                t = layer_table(self.hp, batch, seq_len)
            self._tables[shape] = t
        return t

    def airtime(self, shape: tuple, lk: dict) -> np.ndarray:
        """(L,): [b] = time to ship the hidden state and, before a
        ``shared`` layer, the indices after layer b; 0 after the last."""
        out = [r["out"] * self.a_bytes + r["index_out"] * INDEX_BYTES
               for r in self.table(shape)]
        out[-1] = 0
        packet = (lk["mtu_bytes"] / (lk["rate_bytes_per_s"] * (1.0 - lk["loss_p"]))
                  + lk["t_prop_s"] + lk["t_ack_s"])
        return np.array([math.ceil(x / lk["mtu_bytes"]) if x > 0 else 0
                         for x in out], dtype=np.float64) * packet
