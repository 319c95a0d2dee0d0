"""Plain float64 reference of DeepSeek-V3 planned as pipeline stages on
TPU v5e chip groups, from a deployment file (``bench/configs/<name>.json``:
the published ``config.json`` hyper-parameters at its top level, the
stage device and the links beside them), with no code of the system
under test.

The layer table is written from the DeepSeek-V3 technical report
(arXiv:2412.19437) and the published hyper-parameters:

* MLA (eqs. 1-11): W^DQ (d -> q_lora) and its RMSNorm, W^UQ and W^QR
  (q_lora -> heads x (qk_nope + qk_rope)); W^DKV (d -> kv_lora) and its
  RMSNorm, W^KR (d -> qk_rope); W^UK and W^UV (kv_lora -> heads x
  qk_nope / v_head); W^O (heads x v_head -> d). Only c^KV and k^R are
  cached: kv_lora + qk_rope values a token a layer. Attention runs in
  the latent space (W^UK folded into the query, W^UV applied after the
  weighted sum), over every (query, key) pair.
* FFN: SwiGLU (three matrices) of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; then ``n_routed_experts`` routed and
  ``n_shared_experts`` shared experts of ``moe_intermediate_size``,
  ``num_experts_per_tok`` routed a token, sigmoid affinities against one
  centroid per expert plus a selection bias per expert (eqs. 12-16).
* MTP (eqs. 21-23): per module two RMSNorms, M_k (2d -> d), one
  Transformer block (MLA + MoE), and the shared output head with its
  RMSNorm applied again; embedding and output head shared.

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
  bandwidth); a stage of c chips divides it by c.

A stage holding layers a..b costs the sum of their times plus shipping
the activation after b (ceil(bytes / MTU) packets of MTU / (rate (1 -
loss)) + t_prop + t_ack); it is infeasible (+inf) when its resident
bytes (weights and cache) plus its largest working set exceed the
stage's usable HBM. The bottleneck DP:

  dp_1[b] = C[1, b];  dp_k[b] = min over a < b of max(dp_{k-1}[a], C[a+1, b])

and the answer is dp_n[L]. ``dtype`` runs the same DP one precision down
(the control).
"""

from __future__ import annotations

import math

import numpy as np

INF = float("inf")


def experts_touched(E: int, k: int, T: int) -> float:
    return E * (1.0 - (1.0 - k / E) ** T)


def _mla_params(hp: dict) -> int:
    d, H = hp["hidden_size"], hp["num_attention_heads"]
    qr, kr = hp["q_lora_rank"], hp["kv_lora_rank"]
    dn, dr, dv = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"], hp["v_head_dim"]
    return sum((d * qr, qr,              # W^DQ, its RMSNorm
                qr * H * dn, qr * H * dr,  # W^UQ, W^QR
                d * kr, d * dr, kr,      # W^DKV, W^KR, its RMSNorm
                kr * H * dn, kr * H * dv,  # W^UK, W^UV
                H * dv * d))             # W^O


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
    attn = _mla_params(hp) + 2 * d
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
    """One row per stage candidate (``embed``, ``layer_i``, ``head``)."""
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


class PipelineDeployment:
    """The numbers of one deployment file, as float64 arrays."""

    def __init__(self, cfg: dict):
        if cfg.get("objective") != "bottleneck":
            raise ValueError("the reference prices the 'bottleneck' objective only")
        self.hp = cfg
        self.device = dict(cfg["stage_device"])
        self.links = {k: dict(v) for k, v in cfg["links"].items()}
        self.w_bytes = int(cfg["weight_bytes"])
        self.a_bytes = int(cfg["activation_bytes"])
        self._tables: dict = {}

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

    def link(self, name: str, loss=None, rate_scale: float = 1.0) -> dict:
        lk = dict(self.links[name])
        if loss is not None:
            lk["loss_p"] = float(loss)
        lk["rate_bytes_per_s"] = lk["rate_bytes_per_s"] * rate_scale
        return lk

    def layer_seconds(self, table) -> np.ndarray:
        dev = self.device
        out = []
        for r in table:
            nbytes = (r["streamed"] * self.w_bytes + r["work"] * self.a_bytes
                      + r["cache_read"] * self.a_bytes)
            out.append(max(r["flops"] / dev["peak_flops_per_s"],
                           nbytes / dev["hbm_bytes_per_s"]))
        return np.array(out, dtype=np.float64)

    def local(self, shape: tuple, chips: int) -> np.ndarray:
        """(L, L): [a, b] (0-based, inclusive) = layers a..b on a stage of
        ``chips`` chips; +inf where a > b or the segment does not fit."""
        table = self.table(shape)
        L = len(table)
        t = self.layer_seconds(table)
        res = np.array([r["resident"] * self.w_bytes + r["cache"] * self.a_bytes
                        for r in table], dtype=np.float64)
        work = np.array([r["work"] * self.a_bytes for r in table], dtype=np.float64)
        dev = self.device
        limit = chips * dev["hbm_bytes"] * dev["usable_fraction"]
        out = np.full((L, L), INF)
        for a in range(L):
            held = np.cumsum(res[a:]) + np.maximum.accumulate(work[a:])
            out[a, a:] = np.where(held > limit, INF,
                                  np.cumsum(t[a:]) * (1.0 / chips))
        return out

    def airtime(self, shape: tuple, lk: dict) -> np.ndarray:
        """(L,): [b] = time to ship the activation after layer b; 0 after
        the last."""
        out = [r["out"] * self.a_bytes for r in self.table(shape)]
        out[-1] = 0
        packet = (lk["mtu_bytes"] / (lk["rate_bytes_per_s"] * (1.0 - lk["loss_p"]))
                  + lk["t_prop_s"] + lk["t_ack_s"])
        return np.array([math.ceil(x / lk["mtu_bytes"]) if x > 0 else 0
                         for x in out], dtype=np.float64) * packet


def bottleneck_tables(C, n_max: int, xp=np):
    """(dp, parents) of the bottleneck DP on one stage-cost matrix ``C``
    ((L, L) in the working dtype; every stage the same device): dp
    (n_max, L), parents (n_max, L) with parents[k-1, b] the 0-based last
    layer of stage k-1, -1 for k = 1 or no finite candidate."""
    L = C.shape[0]
    dp = C[0]
    dps = [dp]
    parents = [xp.full((L,), -1, dtype=xp.int32)]
    # candidate a: the previous stages end at layer a, this one covers
    # a+1..b, i.e. C[a+1, b]
    shifted = xp.concatenate([C[1:], xp.full((1, L), INF, dtype=C.dtype)], axis=0)
    for _ in range(2, n_max + 1):
        cand = xp.maximum(dp[:, None], shifted)
        ndp = xp.min(cand, axis=0)
        arg = xp.argmin(cand, axis=0).astype(xp.int32)
        parents.append(xp.where(xp.isfinite(ndp), arg, -1))
        dps.append(ndp)
        dp = ndp
    return xp.stack(dps), xp.stack(parents)


def splits_from(parents: np.ndarray, n: int, L: int) -> tuple[int, ...]:
    """The cuts (1-based layer after which each falls) of the n-stage
    optimum, walking parents back from layer L; () when none."""
    cuts, b = [], L - 1
    for k in range(n, 1, -1):
        a = int(parents[k - 1, b])
        if a < 0:
            return ()
        cuts.append(a + 1)
        b = a
    return tuple(reversed(cuts))


def price(local: np.ndarray, tx: np.ndarray, splits) -> tuple[float, float, float]:
    """(bottleneck, summed stage-local time, summed shipping time) of a
    plan in float64, sums left to right; +inf when malformed or a stage
    does not fit."""
    L = local.shape[0]
    bounds = [0, *[int(s) for s in splits], L]
    if any(not bounds[i] < bounds[i + 1] for i in range(len(bounds) - 1)):
        return INF, INF, INF
    worst = dev = trans = 0.0
    for i in range(len(bounds) - 1):
        a, b = bounds[i], bounds[i + 1] - 1
        seg = float(local[a, b]) + float(tx[b])
        worst = max(worst, seg)
        dev += float(local[a, b])
        trans += float(tx[b]) if bounds[i + 1] < L else 0.0
    if not math.isfinite(worst):
        return INF, INF, INF
    return worst, dev, trans
