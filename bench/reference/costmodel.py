"""Plain float64 reference of the split-inference cost model.

Eqs. 4-8 of arXiv:2507.16594 written out directly from a deployment file
(``bench/configs/<name>.json``), with no code of the system under test:

  segment cost of layers [a, b] on device k
      = T_load + T_alloc + T_infer + T_buffer (+ T_input on device 1)
        + K_b * packet_time                       (b < L: the cut after b)
  K_b = ceil(act_bytes(b) / MTU)
  packet_time = MTU / (rate * (1 - loss)) + t_prop + t_ack
  total latency = sum of segment costs + link setup + prediction feedback

A segment whose weights plus working set exceed the device's memory is
infeasible (+inf). With a contention group of g > 1 transmitters every
link runs at ``mac_efficiency / g`` of its rate. Energy (for budgeted
grids) is ``P_active * local + P_tx * airtime(out) + P_rx * airtime(in)``
per segment; a segment over the budget is infeasible.
"""

from __future__ import annotations

import math

import numpy as np

INF = float("inf")


class Deployment:
    """The numbers of one deployment file, as float64 arrays."""

    def __init__(self, cfg: dict):
        model = cfg["model"]
        rows = [dict(zip(model["layer_fields"], r)) for r in model["layers"]]
        self.name = cfg["name"]
        self.L = len(rows)
        self.input_bytes = int(model["input_bytes"])
        self.t_infer = np.array([r["t_infer_s"] for r in rows], dtype=np.float64)
        self.act = np.array([r["act_bytes"] for r in rows], dtype=np.float64)
        self.param = np.array([r["param_bytes"] for r in rows], dtype=np.float64)
        self.work = np.array([r["work_bytes"] for r in rows], dtype=np.float64)
        self.device = dict(cfg["device"])
        self.protocols = {k: dict(v) for k, v in cfg["protocols"].items()}
        if cfg.get("objective", "sum") != "sum":
            raise ValueError("the reference prices the 'sum' objective only")

    # -- device side ---------------------------------------------------
    def out_bytes(self) -> np.ndarray:
        """(L,) bytes leaving layer b (index b-1); 0 after the last layer."""
        out = self.act.copy()
        out[-1] = 0.0
        return out

    def local_matrix(self, first: bool, device: dict | None = None) -> np.ndarray:
        """(L, L): [a-1, b-1] = device-local latency of layers a..b."""
        d = self.device if device is None else device
        L = self.L
        infer = np.zeros((L, L))
        param = np.zeros((L, L))
        work = np.zeros((L, L))
        for a in range(L):
            infer[a, a:] = np.cumsum(self.t_infer[a:])
            param[a, a:] = np.cumsum(self.param[a:])
            work[a, a:] = np.maximum.accumulate(self.work[a:])
        t = (d["t_model_load_s"] + param * d["model_load_s_per_byte"]
             + d["t_tensor_alloc_s"] + work * d["tensor_alloc_s_per_byte"]
             + infer * d["compute_scale"]
             + d["t_buffer_s"] + self.out_bytes()[None, :] * d["buffer_s_per_byte"])
        if first:
            t = t + d["t_input_load_s"]
        bad = np.tril(np.ones((L, L), dtype=bool), k=-1)
        if d.get("mem_limit_bytes") is not None:
            bad |= (param + work) > d["mem_limit_bytes"]
        return np.where(bad, INF, t)

    # -- link side -----------------------------------------------------
    def link(self, protocol: str, loss=None, rate_scale: float = 1.0,
             contention: int = 1, mac_efficiency: float = 1.0) -> dict:
        lk = dict(self.protocols[protocol])
        if loss is not None:
            lk["loss_p"] = float(loss)
        lk["rate_bytes_per_s"] = lk["rate_bytes_per_s"] * rate_scale
        if contention > 1:
            lk["rate_bytes_per_s"] = (lk["rate_bytes_per_s"]
                                      * (mac_efficiency / contention))
        return lk

    @staticmethod
    def packet_time(lk: dict) -> float:
        return (lk["mtu_bytes"] / (lk["rate_bytes_per_s"] * (1.0 - lk["loss_p"]))
                + lk["t_prop_s"] + lk["t_ack_s"])

    def airtime(self, lk: dict) -> np.ndarray:
        """(L,): [b-1] = time to ship the activation cut after layer b."""
        out = self.out_bytes()
        packets = np.array([math.ceil(x / lk["mtu_bytes"]) if x > 0 else 0
                            for x in out], dtype=np.float64)
        return packets * self.packet_time(lk)

    # -- energy (budgeted grids) ---------------------------------------
    def energy_matrix(self, local: np.ndarray, lk: dict,
                      device: dict | None = None) -> np.ndarray:
        """(L, L) Joules of each segment, +inf where ``local`` is."""
        d = self.device if device is None else device
        air = self.airtime(lk)
        air_in = np.zeros(self.L)
        air_in[1:] = air[:-1]
        with np.errstate(invalid="ignore"):
            e = np.where(np.isfinite(local), d.get("active_power_w", 0.0) * local, INF)
        return (e + lk.get("tx_power_w", 0.0) * air[None, :]
                + lk.get("rx_power_w", 0.0) * air_in[:, None])


def price(dep: Deployment, local_first: np.ndarray, local_rest: np.ndarray,
          tx: np.ndarray, splits, lk: dict) -> tuple[float, float, float]:
    """(device_s, transmission_s, total_latency_s) of one split plan,
    summed left to right; +inf when a segment is infeasible or the plan
    is malformed."""
    L = dep.L
    bounds = [0, *[int(s) for s in splits], L]
    if any(not bounds[i] < bounds[i + 1] for i in range(len(bounds) - 1)):
        return INF, INF, INF
    dev = 0.0
    trans = 0.0
    for i in range(len(bounds) - 1):
        m = local_first if i == 0 else local_rest
        dev += float(m[bounds[i], bounds[i + 1] - 1])
        if bounds[i + 1] < L:
            trans += float(tx[bounds[i + 1] - 1])
    total = dev + trans + lk["t_setup_s"] + lk["t_feedback_s"]
    return dev, trans, total
