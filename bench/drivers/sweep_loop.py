"""Closed-loop what-if planning: one operator client calling ``sweep``.

The traffic file (``bench/traffic/<name>.json``, ``"driver":
"sweep_loop"``) names every axis of the program's ``ScenarioGrid``:
protocols, fleet sizes, loss and rate-scale axes (literal lists, or
draws from the seed for every call), contention groups, MAC efficiency,
energy budgets (literal Joules, or a percentile of the deployment's own
energy tensor), compression factors, device and link power overrides,
and the solver and backend. Every call has the same shapes, so one
program serves the whole window.

Correctness: a sample of every call's rows, drawn from the seed, is
kept and compared after the window with the float64 reference DP of
``bench/reference`` (feasibility, cost, the regret of the served
splits, and the per-row device / transmission / total latencies).
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass

import numpy as np

from bench.reference import dp as refdp
from bench.reference.costmodel import INF, Deployment, price

# rows of every call kept for the comparison (drawn from the seed)
ROWS_PER_CALL = 512
# calls whose axes and samples are drawn before the window
MAX_CALLS = 512
# reference rows per DP block (bounds the (R, L, L) working set)
REF_BLOCK = 2048


# ---------------------------------------------------------------------------
# Traffic: the grid axes of every call
# ---------------------------------------------------------------------------


def _axis(spec, rng) -> tuple:
    """A literal list, or ``{"draws", "law", "low", "high", "base"}``."""
    if isinstance(spec, list):
        return tuple(spec)
    n = int(spec["draws"])
    lo, hi = float(spec["low"]), float(spec["high"])
    if spec["law"] == "uniform":
        vals = rng.uniform(lo, hi, n)
    elif spec["law"] == "log_uniform":
        vals = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    else:
        raise ValueError(f"unknown law {spec['law']!r}")
    out = tuple(float(v) for v in vals)
    return ((None,) + out) if spec.get("base") else out


@dataclass
class Grid:
    """One call's axes, in the program's enumeration order."""

    protocols: tuple
    n_devices: tuple
    loss_p: tuple
    rate_scale: tuple
    contention: tuple
    budgets: tuple
    compression: tuple

    @property
    def shape(self) -> tuple:
        return (len(self.n_devices), len(self.protocols), len(self.loss_p),
                len(self.rate_scale), len(self.contention), len(self.budgets),
                len(self.compression))

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    def scenario(self, idx: int) -> tuple:
        """(n, protocol, loss, rate, contention, budget, compression) of
        row ``idx``: fleet size major, then protocol, loss, rate,
        contention, budget, compression (one model, one device mix)."""
        i = np.unravel_index(idx, self.shape)
        return (self.n_devices[i[0]], self.protocols[i[1]], self.loss_p[i[2]],
                self.rate_scale[i[3]], self.contention[i[4]],
                self.budgets[i[5]], self.compression[i[6]])


class Traffic:
    """The deployment plus the draws of every call, from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg = cfg
        self.spec = traffic
        g = traffic["grid"]
        self.dep = Deployment(cfg)
        self.device = {**self.dep.device, **g.get("device_overrides", {})}
        for name in self.dep.protocols:
            self.dep.protocols[name].update(g.get("link_overrides", {}))
        self.protocols = tuple(g["protocols"])
        self.mac_efficiency = float(g.get("mac_efficiency", 1.0))
        if any(float(c) != 1.0 for c in g.get("compression_factors", [1.0])):
            raise ValueError("the reference prices compression factor 1.0 only")
        self.budgets = tuple(self._budget(b) for b in g.get("energy_budgets", [None]))
        self.rows_per_call = int(traffic.get("check", {}).get("rows_per_call",
                                                              ROWS_PER_CALL))
        rng = np.random.default_rng([seed, 1])
        self.grids = [self._draw(g, rng) for _ in range(MAX_CALLS + 1)]
        pick = np.random.default_rng([seed, 2])
        size = self.grids[0].size
        k = min(self.rows_per_call, size)
        self.samples = [np.sort(pick.choice(size, size=k, replace=False))
                        for _ in range(MAX_CALLS)]

    def _budget(self, b):
        if b is None or isinstance(b, (int, float)):
            return None if b is None else float(b)
        # a percentile of the deployment's own energy tensor at the
        # largest fleet size, on the named protocol's base link
        lk = self.dep.link(b["protocol"])
        n = max(self.spec["grid"]["n_devices"])
        e = [self.dep.energy_matrix(self.dep.local_matrix(k == 0, self.device),
                                    lk, self.device) for k in range(n)]
        fin = np.concatenate([x[np.isfinite(x)] for x in e])
        return float(np.percentile(fin, float(b["energy_percentile"])))

    def _draw(self, g: dict, rng) -> Grid:
        return Grid(
            protocols=self.protocols,
            n_devices=tuple(int(n) for n in g["n_devices"]),
            loss_p=_axis(g["loss_p"], rng),
            rate_scale=_axis(g["rate_scale"], rng),
            contention=tuple(int(c) for c in g.get("contention_groups", [1])),
            budgets=self.budgets,
            compression=tuple(float(c) for c in g.get("compression_factors", [1.0])),
        )


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def program_objects(cfg: dict, traffic: Traffic):
    """The program's profile objects, built from the deployment file."""
    from repro.core.latency import DeviceProfile, LayerCost, LinkProfile, ModelCostProfile

    m = cfg["model"]
    layers = tuple(LayerCost(**dict(zip(m["layer_fields"], r))) for r in m["layers"])
    profile = ModelCostProfile(name=m["name"], layers=layers,
                               input_bytes=int(m["input_bytes"]))
    device = DeviceProfile(**traffic.device)
    links = {p: LinkProfile(**traffic.dep.protocols[p]) for p in traffic.protocols}
    return profile, device, links


class Driver:
    """``setup`` (warm call), ``window`` (closed loop), ``check``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg = cfg
        self.traffic = Traffic(cfg, traffic, seed)
        self.solver = traffic.get("solver", "batched_dp")
        self.backend = traffic.get("backend", "pallas")
        self.calls: list[dict] = []

    def _grid(self, g: Grid):
        from repro.core.sweep import ScenarioGrid

        return ScenarioGrid(
            models={self.profile.name: self.profile}, links=self.links,
            n_devices=g.n_devices, loss_p=g.loss_p, rate_scale=g.rate_scale,
            devices=(self.device,), contention_groups=g.contention,
            energy_budgets=g.budgets, mac_efficiency=self.traffic.mac_efficiency,
            compression_factors=g.compression)

    def setup(self) -> None:
        from repro.core.sweep import sweep

        self.sweep = sweep
        self.profile, self.device, self.links = program_objects(
            self.cfg, self.traffic)
        # the warm call uses the spare last draw: same shapes as the window
        self.sweep(self._grid(self.traffic.grids[-1]), solver=self.solver,
                   backend=self.backend)

    def window(self, seconds: float) -> tuple[float, float]:
        """Back-to-back calls while the window is open; every call that
        started finishes and counts. Returns (start, end) on the
        benchmark's clock."""
        import jax

        clock = time.perf_counter
        start = clock()
        i = len(self.calls)
        while i < MAX_CALLS:
            g = self.traffic.grids[i]
            grid = self._grid(g)
            t0 = clock()
            with jax.profiler.TraceAnnotation("bench.sweep_call"):
                res = self.sweep(grid, solver=self.solver, backend=self.backend)
            t1 = clock()
            rows = res.rows
            kept = {}
            for idx in self.traffic.samples[i]:
                if idx < len(rows):
                    r = rows[idx]
                    sc = r.scenario
                    kept[int(idx)] = (
                        (sc.n_devices, sc.protocol, sc.loss_p, sc.rate_scale,
                         sc.contention, sc.energy_budget, sc.compression),
                        tuple(r.splits), bool(r.feasible), r.objective_cost_s,
                        r.total_latency_s, r.device_s, r.transmission_s)
            self.calls.append({
                "i": i, "scenarios": len(rows), "t0": t0, "t1": t1,
                "build_s": res.build_time_s, "solve_s": res.solve_time_s,
                "kept": kept})
            del res, rows
            i += 1
            if t1 - start >= seconds:
                break
        return start, self.calls[-1]["t1"]

    # -- records the metric readers use ------------------------------------
    def records(self) -> dict:
        return {"calls": self.calls, "ns": self.traffic.grids[0].n_devices,
                "L": self.traffic.dep.L, "grid_size": self.traffic.grids[0].size}

    def attempted(self) -> tuple[int, int]:
        """(calls made, calls that returned fewer rows than the grid)."""
        size = self.traffic.grids[0].size
        return len(self.calls), sum(c["scenarios"] != size for c in self.calls)

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        for name in ("sweep", "profile", "device", "links"):
            self.__dict__.pop(name, None)

    def check(self, limits: dict) -> dict:
        answers = [(c["i"], c["kept"]) for c in self.calls]
        return compare(self.traffic, answers, limits)


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------


def reference(traffic: Traffic, rows: list[tuple[int, int]]) -> dict:
    """float64 optimum of each (call, row): {(call, idx): (cost, splits)}."""
    dep = traffic.dep
    first = dep.local_matrix(True, traffic.device)
    rest = dep.local_matrix(False, traffic.device)
    scen = {}
    for call, idx in rows:
        scen[(call, idx)] = traffic.grids[call].scenario(idx)
    out = {}
    keys = list(scen)
    for lo in range(0, len(keys), REF_BLOCK):
        block = keys[lo:lo + REF_BLOCK]
        tx, first_row, masks, ns = [], [], [], []
        for key in block:
            n, p, loss, rate, con, budget, _ = scen[key]
            lk = dep.link(p, loss, rate, con, traffic.mac_efficiency)
            t = dep.airtime(lk)
            tx.append(t)
            ns.append(n)
            if budget is not None:
                ef = dep.energy_matrix(first, lk, traffic.device) > budget
                er = dep.energy_matrix(rest, lk, traffic.device) > budget
            else:
                ef = er = None
            masks.append((ef, er))
        tx = np.stack(tx)
        ns = np.array(ns)
        fr = first[0][None, :] + tx
        budgeted = any(m[0] is not None for m in masks)
        if budgeted:
            fr = np.where(np.stack([m[0][0] if m[0] is not None else
                                    np.zeros(dep.L, bool) for m in masks]), INF, fr)
            er = np.stack([m[1] if m[1] is not None else
                           np.zeros((dep.L, dep.L), bool) for m in masks])

            def seg(k):
                return np.where(er, INF, rest[None] + tx[:, None, :])
        else:
            def seg(k):
                return rest[None] + tx[:, None, :]
        dps, parents = refdp.tables(fr, seg, int(ns.max()))
        splits = refdp.splits_from(parents, ns, dep.L)
        for r, key in enumerate(block):
            n = ns[r]
            out[key] = (float(dps[r, n - 1, dep.L - 1]), tuple(splits[r, :n - 1]))
    return out


def compare(traffic: Traffic, answers, limits: dict) -> dict:
    """Every number compared, ``{name: value}``:

    * ``missing`` - sampled rows the call did not return, or returned
      for another scenario than the grid's enumeration puts there;
    * ``feasibility`` - rows whose feasibility differs;
    * ``cost_gap`` - widest relative gap of the served objective, and
      of the served total latency, from the reference optimum;
    * ``regret`` - widest relative excess of the served splits, priced
      in float64, over the reference optimum;
    * ``rows_gap`` - widest relative gap of the served device and
      transmission latencies from the float64 price of the served
      splits."""
    dep = traffic.dep
    first = dep.local_matrix(True, traffic.device)
    rest = dep.local_matrix(False, traffic.device)
    wanted = []
    for call, kept in answers:
        for idx in traffic.samples[call]:
            wanted.append((call, int(idx)))
    ref = reference(traffic, wanted)
    kept_by = dict(answers)
    missing = feas = 0
    cost_gap = regret = rows_gap = 0.0
    for call, idx in wanted:
        ans = kept_by[call].get(idx)
        sc = traffic.grids[call].scenario(idx)
        if ans is None or ans[0] != sc:
            missing += 1
            continue
        _, splits, feasible, obj, total, dev_s, tx_s = ans
        ref_cost, _ = ref[(call, idx)]
        ref_feasible = math.isfinite(ref_cost)
        if feasible != ref_feasible:
            feas += 1
            continue
        if not ref_feasible:
            continue
        n, p, loss, rate, con, budget, _ = sc
        lk = dep.link(p, loss, rate, con, traffic.mac_efficiency)
        tx = dep.airtime(lk)
        if len(splits) != n - 1:
            regret = INF
            continue
        d, t, tot = price(dep, first, rest, tx, splits, lk)
        if budget is not None and math.isfinite(d):
            bounds = [0, *splits, dep.L]
            for i in range(n):
                m = first if i == 0 else rest
                e = dep.energy_matrix(m, lk, traffic.device)
                if e[bounds[i], bounds[i + 1] - 1] > budget:
                    d = t = tot = INF
        regret = max(regret, (d + t - ref_cost) / ref_cost)
        ref_total = ref_cost + lk["t_setup_s"] + lk["t_feedback_s"]
        cost_gap = max(cost_gap, abs(obj - ref_cost) / ref_cost,
                       abs(total - ref_total) / ref_total)
        for got, want in ((dev_s, d), (tx_s, t)):
            rows_gap = max(rows_gap, abs(got - want) / max(abs(want), 1e-30))
    return {"missing": missing, "feasibility": feas, "cost_gap": cost_gap,
            "regret": regret, "rows_gap": rows_gap}
