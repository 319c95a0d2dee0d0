"""Plain float64 reference of DeepSeek-V3 as pipeline stages on TPU v5e.

Written from the DeepSeek-V3 technical report (arXiv:2412.19437) and the
published ``config.json`` hyper-parameters (their key names), with no
code of the system under test (nothing from ``repro``):

* MLA (report eqs. 1-11): W^DQ (d -> q_lora) and its RMSNorm, W^UQ and
  W^QR (q_lora -> heads x (qk_nope + qk_rope)); W^DKV (d -> kv_lora) and
  its RMSNorm, W^KR (d -> qk_rope); W^UK and W^UV (kv_lora -> heads x
  qk_nope / v_head); W^O (heads x v_head -> d). Only c^KV and k^R are
  cached: kv_lora + qk_rope values a token a layer. Attention runs in the
  latent space (W^UK folded into the query, W^UV applied after the
  weighted sum), over every (query, key) pair.
* FFN: SwiGLU (three matrices) of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; then ``n_routed_experts`` routed
  experts and ``n_shared_experts`` shared ones of
  ``moe_intermediate_size``, ``num_experts_per_tok`` routed a token,
  sigmoid affinities against one centroid per expert plus a bias per
  expert for selection (eqs. 12-16).
* MTP (eqs. 21-23): per module two RMSNorms, M_k (2d -> d), one
  Transformer block (MLA + MoE), and the shared output head (with its
  RMSNorm) applied again; the embedding and output head are shared with
  the main model.

Departures from the report, each a choice of the planner's model:

* MTP is folded into the ``head`` node: it reads both the last hidden
  state and the embedding of the next token, so a stage cut between them
  would ship two tensors. The embedding copy it reads is resident there.
* Weights and cache are bf16 (2 bytes); the published checkpoint is FP8,
  which v5e cannot multiply.
* Routing is uniform: a step of T tokens touches E (1 - (1 - k/E)^T)
  routed experts and reads only those; the embedding lookup likewise
  reads V (1 - (1 - 1/V)^T) rows. Every other weight is read once a
  step, the output head once per application, the latent cache once.
* A layer's time is one chip's roofline, max(FLOPs / peak, bytes /
  bandwidth); c chips divide it by c.

The bottleneck DP: dp_1[b] = C_1[1, b]; dp_k[b] = min over a < b of
max(dp_{k-1}[a], C_k[a+1, b]); the answer is dp_n[L].
"""

from __future__ import annotations

import math

import numpy as np

INF = float("inf")

# TPU v5e, one chip (Google Cloud documentation)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
HBM_BYTES = 16 * 1024**3
USABLE = 0.9
WEIGHT_BYTES = 2  # bf16
ACT_BYTES = 2  # bf16 activations and latent cache


def experts_touched(E: int, k: int, T: int) -> float:
    return E * (1.0 - (1.0 - k / E) ** T)


def _mla(hp: dict) -> dict:
    """Each MLA weight matrix and norm, by its name in the report."""
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    qr, kr = hp["q_lora_rank"], hp["kv_lora_rank"]
    dn, dr, dv = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"], hp["v_head_dim"]
    return {"W_DQ": d * qr, "q_norm": qr, "W_UQ": qr * H * dn,
            "W_QR": qr * H * dr, "W_DKV": d * kr, "W_KR": d * dr,
            "kv_norm": kr, "W_UK": kr * H * dn, "W_UV": kr * H * dv,
            "W_O": H * dv * d}


def _mla_flops(hp: dict, B: int, S: int, K: int) -> float:
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    qr, kr = hp["q_lora_rank"], hp["kv_lora_rank"]
    dn, dr, dv = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"], hp["v_head_dim"]
    T = B * S
    return (2.0 * T * d * qr            # c^Q
            + 2.0 * T * qr * H * dn     # q^C
            + 2.0 * T * qr * H * dr     # q^R
            + 2.0 * T * d * kr          # c^KV
            + 2.0 * T * d * dr          # k^R
            + 2.0 * T * H * dn * kr     # W^UK folded into q^C
            + 2.0 * B * H * S * K * (kr + dr)  # scores
            + 2.0 * B * H * S * K * kr  # weighted sum of latents
            + 2.0 * T * H * kr * dv     # W^UV
            + 2.0 * T * H * dv * d)     # W^O


def _block(hp: dict, dense: bool, B: int, S: int, K: int) -> dict:
    """One decoder block (MLA + FFN, two RMSNorms)."""
    d = hp["hidden_size"]
    T = B * S
    attn = sum(_mla(hp).values()) + 2 * d
    flops = _mla_flops(hp, B, S, K)
    if dense:
        ffn = 3 * d * hp["intermediate_size"]
        flops += 2.0 * T * ffn
        resident, streamed = attn + ffn, float(attn + ffn)
    else:
        E, k = hp["n_routed_experts"], hp["num_experts_per_tok"]
        Es = hp["n_shared_experts"]
        expert = 3 * d * hp["moe_intermediate_size"]
        router = d * E + E  # centroids e_i and selection biases b_i
        flops += 2.0 * T * k * expert + 2.0 * T * Es * expert
        flops += 2.0 * T * d * E  # affinities u_t . e_i
        resident = attn + E * expert + Es * expert + router
        streamed = (attn + experts_touched(E, k, T) * expert + Es * expert
                    + router)
    cache = B * K * (hp["kv_lora_rank"] + hp["qk_rope_head_dim"])
    return {"flops": flops, "resident": resident, "streamed": streamed,
            "cache": cache}


def layer_table(hp: dict, batch: int, seq: int, kv_len: int | None = None):
    """One row per stage candidate (``embed``, ``layer_i``, ``head``):
    name, flops, resident and streamed parameters, resident and read
    cache elements, output and working-set elements."""
    d, V = hp["hidden_size"], hp["vocab_size"]
    T = batch * seq
    K = seq if kv_len is None else kv_len
    act = T * d
    rows_read = experts_touched(V, 1, T) * d
    table = [dict(name="embed", flops=0.0, resident=V * d, streamed=rows_read,
                  cache=0, cache_read=0, out=act, work=2 * act)]
    for i in range(hp["num_hidden_layers"]):
        b = _block(hp, i < hp["first_k_dense_replace"], batch, seq, K)
        table.append(dict(name=f"layer_{i}", flops=b["flops"],
                          resident=b["resident"], streamed=b["streamed"],
                          cache=b["cache"], cache_read=b["cache"], out=act,
                          work=2 * act))
    head = V * d
    flops = 2.0 * T * d * V
    resident, streamed, cache = d + head, float(d + head), 0
    for _ in range(hp["num_nextn_predict_layers"]):
        b = _block(hp, False, batch, seq, K)
        own = d + d + 2 * d * d + d  # two RMSNorms, M_k, the head's RMSNorm
        flops += 2.0 * T * 2 * d * d + b["flops"] + 2.0 * T * d * V
        resident += own + b["resident"] + V * d  # + the embedding copy
        streamed += own + b["streamed"] + rows_read + head
        cache += b["cache"]
    table.append(dict(name="head", flops=flops, resident=resident,
                      streamed=streamed, cache=cache, cache_read=cache,
                      out=T * V, work=act + T * V))
    return table


def param_totals(hp: dict) -> dict:
    """Main-model parameters (embedding, blocks, final norm, output head),
    those a token activates (all but the routed experts it does not
    pick), and the MTP modules' own."""
    d, V = hp["hidden_size"], hp["vocab_size"]
    E, k = hp["n_routed_experts"], hp["num_experts_per_tok"]
    main = 2 * V * d + d
    idle = 0
    for i in range(hp["num_hidden_layers"]):
        dense = i < hp["first_k_dense_replace"]
        main += _block(hp, dense, 1, 1, 1)["resident"]
        if not dense:
            idle += (E - k) * 3 * d * hp["moe_intermediate_size"]
    b = _block(hp, False, 1, 1, 1)
    mtp = hp["num_nextn_predict_layers"] * (3 * d + 2 * d * d + b["resident"])
    return {"main": main, "active": main - idle, "mtp_own": mtp}


# -- the stage costs and the bottleneck DP ------------------------------------


def layer_seconds(table) -> np.ndarray:
    """(L,) one chip's time of each row."""
    out = []
    for r in table:
        nbytes = (r["streamed"] * WEIGHT_BYTES + r["work"] * ACT_BYTES
                  + r["cache_read"] * ACT_BYTES)
        out.append(max(r["flops"] / PEAK_FLOPS, nbytes / HBM_BW))
    return np.array(out, dtype=np.float64)


def segment_costs(table, chips: int, link: dict) -> np.ndarray:
    """(L, L): [a, b] (0-based, inclusive) = layers a..b on a stage of
    ``chips`` chips plus shipping the activation after b; +inf where the
    resident bytes and the largest working set exceed the stage's usable
    HBM or a > b."""
    L = len(table)
    t = layer_seconds(table)
    res = np.array([r["resident"] * WEIGHT_BYTES + r["cache"] * ACT_BYTES
                    for r in table], dtype=np.float64)
    work = np.array([r["work"] * ACT_BYTES for r in table], dtype=np.float64)
    out = np.array([r["out"] * ACT_BYTES for r in table], dtype=np.float64)
    out[-1] = 0.0
    packet = (link["mtu_bytes"] / (link["rate_bytes_per_s"] * (1.0 - link["loss_p"]))
              + link["t_prop_s"] + link["t_ack_s"])
    tx = np.array([math.ceil(x / link["mtu_bytes"]) if x > 0 else 0
                   for x in out], dtype=np.float64) * packet
    limit = chips * HBM_BYTES * USABLE
    C = np.full((L, L), INF)
    for a in range(L):
        local = np.cumsum(t[a:]) * (1.0 / chips)
        held = np.cumsum(res[a:]) + np.maximum.accumulate(work[a:])
        C[a, a:] = np.where(held > limit, INF, local + tx[a:])
    return C


def bottleneck_dp(C: np.ndarray, n: int) -> tuple[float, tuple[int, ...]]:
    """Least bottleneck over n contiguous stages and its cuts (1-based
    layer after which each cut falls)."""
    L = C.shape[0]
    dp = C[0].copy()
    parents = []
    for _ in range(2, n + 1):
        cand = np.full((L, L), INF)
        cand[:-1] = np.maximum(dp[:-1, None], C[1:])  # [a, b]: cut after a
        arg = np.argmin(cand, axis=0)
        dp = cand[arg, np.arange(L)]
        parents.append(arg)
    if not math.isfinite(dp[L - 1]):
        return INF, ()
    cuts, b = [], L - 1
    for arg in reversed(parents):
        a = int(arg[b])
        cuts.append(a + 1)
        b = a
    return float(dp[L - 1]), tuple(reversed(cuts))


def plan_cost(C: np.ndarray, cuts) -> float:
    """The bottleneck of a given plan, priced in float64."""
    L = C.shape[0]
    bounds = [0, *cuts, L]
    return max(C[bounds[i], bounds[i + 1] - 1] for i in range(len(bounds) - 1))
