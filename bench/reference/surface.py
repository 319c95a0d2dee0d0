"""Plain reference of a degradation surface and of the decision served
from it.

A surface, per protocol, is a grid of link states: packet times
``nominal * scale`` for every requested scale plus the saturation floor
``MTU / rate + t_prop``, times the requested losses. At each node the
link is re-fitted to the state (the loss clamped into [0, 0.9], the
residual of the packet time over loss-free serialisation and
propagation moved into the per-packet ack time), the exact DP picks the
splits, the chunk size that minimises the plan's summed airtime is
chosen among ``{MTU, 3/4 MTU, MTU/2, 1200, 250}`` (those at most the
MTU, smallest first, first strict minimum), and the node stores the
plan's end-to-end latency at that chunk, setup and feedback included.

The decision served at a link state is the argmin over protocols of
the bilinearly interpolated node latency, each protocol taking its
nearest node's plan; a state outside any protocol's envelope (a packet
time above its largest node, a loss off its loss axis) has no surface
decision. A served decision changes only when the surface's is more
than 10% faster than the current plan priced at the current state.
"""

from __future__ import annotations

import math
from bisect import bisect_right

import numpy as np

from bench.reference import dp as refdp
from bench.reference.costmodel import INF, Deployment, price

LOSS_CLAMP = 0.9
REPLAN_THRESHOLD = 0.10


def axes(base: dict, pt_scale, loss_p):
    floor = base["mtu_bytes"] / base["rate_bytes_per_s"] + base["t_prop_s"]
    nominal = Deployment.packet_time(base)
    pts = tuple(sorted({nominal * s for s in pt_scale} | {floor}))
    losses = tuple(sorted({base["loss_p"] if lp is None else lp for lp in loss_p}))
    return pts, losses


def refit(base: dict, pt: float, loss: float) -> dict:
    loss = min(max(loss, 0.0), LOSS_CLAMP)
    serial = base["mtu_bytes"] / (base["rate_bytes_per_s"] * (1.0 - loss))
    return {**base, "t_ack_s": max(0.0, pt - serial - base["t_prop_s"]),
            "loss_p": loss}


def best_chunk(lk: dict, cut_bytes) -> int:
    mtu = lk["mtu_bytes"]
    cands = sorted({c for c in (mtu, mtu * 3 // 4, mtu // 2, 1200, 250) if 0 < c <= mtu})
    best, best_t = mtu, INF
    for c in cands:
        pt = c / (lk["rate_bytes_per_s"] * (1.0 - lk["loss_p"])) + lk["t_prop_s"] + lk["t_ack_s"]
        t = sum((math.ceil(b / c) if b > 0 else 0) * pt for b in cut_bytes)
        if t < best_t:
            best, best_t = c, t
    return best


class SurfaceReference:
    """Reference node decisions for one deployment's protocols."""

    def __init__(self, dep: Deployment, device: dict | None = None, dtype=np.float64,
                 xp=np):
        self.dep = dep
        self.xp = xp
        self.first = dep.local_matrix(True, device)
        self.rest = dep.local_matrix(False, device)
        self.dtype = dtype
        self.out = dep.out_bytes()

    def node_latency(self, lk: dict, splits) -> tuple[int, float]:
        """(chunk, end-to-end latency) of a plan at a node's link."""
        chunk = best_chunk(lk, [self.out[b - 1] for b in splits])
        tuned = {**lk, "mtu_bytes": chunk}
        _, _, total = price(self.dep, self.first, self.rest, self.dep.airtime(tuned),
                            splits, tuned)
        return chunk, total

    def optimum(self, links: list[dict], n: int) -> tuple[np.ndarray, np.ndarray]:
        """(cost, splits) of the DP at each link for fleet size ``n``,
        in this reference's dtype."""
        dt, xp = self.dtype, self.xp
        tx = xp.asarray(np.stack([self.dep.airtime(lk) for lk in links]), dtype=dt)
        first = xp.asarray(self.first, dtype=dt)
        rest = xp.asarray(self.rest, dtype=dt)
        fr = first[0][None, :] + tx
        dps, parents = refdp.tables(fr, lambda k: rest[None] + tx[:, None, :], n, xp=xp)
        ns = np.full(len(links), n)
        cost = np.asarray(dps[:, n - 1, -1].astype(xp.float32), dtype=np.float64)
        return cost, refdp.splits_from(np.asarray(parents), ns, self.dep.L)

    def compare(self, surface: dict, pt_scale, loss_p, sizes) -> dict:
        """Numbers for one adopted surface family ``{n: {protocol:
        (pts, losses, splits, chunk, latency)}}`` built for the request
        (``pt_scale``, ``loss_p``, ``sizes``)."""
        out = {"surf_missing": 0, "surf_feasibility": 0, "surf_regret": 0.0,
               "surf_latency_gap": 0.0, "surf_chunk": 0}
        for n in sizes:
            fam = surface.get(n)
            if fam is None:
                out["surf_missing"] += 1
                continue
            for name, base in self.dep.protocols.items():
                pts, losses = axes(base, pt_scale, loss_p)
                got = fam.get(name)
                if got is None or tuple(got[0]) != pts or tuple(got[1]) != losses:
                    out["surf_missing"] += 1
                    continue
                _, _, splits, chunks, lats = got
                links = [refit(base, pt, lp) for pt in pts for lp in losses]
                cost, _ = self.optimum(links, n)
                G = len(losses)
                for g, lk in enumerate(links):
                    i, j = divmod(g, G)
                    sp = tuple(int(x) for x in splits[i][j])
                    feasible = math.isfinite(lats[i][j]) and all(x > 0 for x in sp)
                    if feasible != math.isfinite(cost[g]):
                        out["surf_feasibility"] += 1
                        continue
                    if not feasible:
                        continue
                    d, t, _ = price(self.dep, self.first, self.rest,
                                    self.dep.airtime(lk), sp, lk)
                    out["surf_regret"] = max(out["surf_regret"],
                                             (d + t - cost[g]) / cost[g])
                    chunk, lat = self.node_latency(lk, sp)
                    out["surf_chunk"] += int(chunk != int(chunks[i][j]))
                    out["surf_latency_gap"] = max(out["surf_latency_gap"],
                                                  abs(lats[i][j] - lat) / lat)
        return out

    def build(self, pt_scale, loss_p, sizes) -> dict:
        """The reference's own surface family, in the layout ``compare``
        takes: what the control puts in the program's place."""
        fam = {}
        for n in sizes:
            fam[n] = {}
            for name, base in self.dep.protocols.items():
                pts, losses = axes(base, pt_scale, loss_p)
                links = [refit(base, pt, lp) for pt in pts for lp in losses]
                cost, splits = self.optimum(links, n)
                T, G = len(pts), len(losses)
                sp = np.full((T, G, n - 1), -1, dtype=np.int64)
                ch = np.zeros((T, G), dtype=np.int64)
                lat = np.full((T, G), INF)
                for g, lk in enumerate(links):
                    i, j = divmod(g, G)
                    if not math.isfinite(cost[g]):
                        continue
                    s = tuple(int(x) for x in splits[g])
                    sp[i, j] = s
                    c = best_chunk(lk, [self.out[b - 1] for b in s])
                    tuned = {**lk, "mtu_bytes": c}
                    tx = self.dep.airtime(tuned).astype(self.dtype)
                    bounds = [0, *s, self.dep.L]
                    tot = self.dtype(0)
                    for k in range(n):
                        m = self.first if k == 0 else self.rest
                        tot = tot + self.dtype(m[bounds[k], bounds[k + 1] - 1])
                        if bounds[k + 1] < self.dep.L:
                            tot = tot + tx[bounds[k + 1] - 1]
                    tot = tot + self.dtype(lk["t_setup_s"]) + self.dtype(lk["t_feedback_s"])
                    ch[i, j] = c
                    lat[i, j] = float(tot)
                fam[n][name] = (pts, losses, sp, ch, lat)
        return fam


# ---------------------------------------------------------------------------
# The decision served from a surface
# ---------------------------------------------------------------------------


def _cell(axis, x, clamp_low=False):
    if x <= axis[0]:
        return 0, 0, 0.0, clamp_low or x == axis[0]
    if x >= axis[-1]:
        n = len(axis) - 1
        return n, n, 0.0, x == axis[-1]
    i = bisect_right(axis, x) - 1
    if axis[i] == x:
        return i, i, 0.0, True
    return i, i + 1, (x - axis[i]) / (axis[i + 1] - axis[i]), True


def lookup(family: dict, states: dict):
    """(protocol, splits, chunk, latency) the surface decides at
    ``states``, or None outside the envelope / with nothing feasible."""
    best = None
    best_lat = INF
    for name, (pt, lp) in states.items():
        pts, losses, splits, chunks, lats = family[name]
        i0, i1, wt, ok_t = _cell(pts, pt, True)
        j0, j1, wl, ok_l = _cell(losses, min(lp, LOSS_CLAMP))
        if not (ok_t and ok_l):
            return None
        ni, nj = (i1 if wt >= 0.5 else i0), (j1 if wl >= 0.5 else j0)
        sp = tuple(int(x) for x in splits[ni][nj])
        if not math.isfinite(lats[ni][nj]) or any(x < 0 for x in sp):
            continue
        lat = 0.0
        for w, z in (((1 - wt) * (1 - wl), lats[i0][j0]), (wt * (1 - wl), lats[i1][j0]),
                     ((1 - wt) * wl, lats[i0][j1]), (wt * wl, lats[i1][j1])):
            if w:
                lat += w * z
        if lat < best_lat:
            best_lat = lat
            best = (name, sp, int(chunks[ni][nj]), lat)
    return best


def served_ok(ref: SurfaceReference, family: dict, states: dict, prev, served) -> bool:
    """Whether ``served`` (protocol, splits, chunk) is the decision the
    surface ``family`` gives at ``states`` when ``prev`` was current."""
    hit = lookup(family, states)
    if hit is None or prev is None:
        return served == (prev if hit is None else hit[:3])
    if hit[:3] == prev:
        return served == prev
    name, splits, chunk = prev
    pt, lp = states[name]
    lk = {**refit(ref.dep.protocols[name], pt, lp), "mtu_bytes": chunk}
    _, _, cur = price(ref.dep, ref.first, ref.rest, ref.dep.airtime(lk), splits, lk)
    bar = cur * (1 - REPLAN_THRESHOLD)
    if abs(hit[3] - bar) <= 1e-9 * bar:  # on the threshold: either is sound
        return served in (prev, hit[:3])
    return served == (hit[:3] if hit[3] < bar else prev)
