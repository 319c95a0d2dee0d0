"""Closed-loop pipeline-stage what-ifs: one operator client calling
``pipeline_grid`` and then ``sweep``.

The traffic file (``bench/traffic/<name>.json``, ``"driver":
"pipeline_loop"``) draws, for every call, the step shapes (prefills of
``batch`` x ``seq_len`` tokens, decode steps of ``batch`` sequences
against ``kv_len`` cached positions) and the loss and rate-scale axes,
and names the chip-group sizes, stage counts and links. The deployment
file gives the model's published hyper-parameters, the stage device and
the links. Every call has the same grid shape, so one program serves
the whole window.

Correctness: a sample of every call's rows, drawn from the seed, is
kept and compared after the window with the float64 reference of
``bench/reference/pipeline.py`` (feasibility, the bottleneck, the regret
of the served cuts, and the per-row summed stage and shipping times),
under the limits of ``bench/checks/<cell>.json``.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass

import numpy as np

from bench.reference.pipeline import (INF, PipelineDeployment,
                                      bottleneck_tables, price, splits_from)

# rows of every call kept for the comparison (drawn from the seed)
ROWS_PER_CALL = 512
# calls whose axes and samples are drawn before the window
MAX_CALLS = 256


# ---------------------------------------------------------------------------
# Traffic: the shapes and axes of every call
# ---------------------------------------------------------------------------


def _draw(spec, rng, n: int) -> tuple:
    """``n`` draws of ``{"law", "low", "high"}``."""
    lo, hi = float(spec["low"]), float(spec["high"])
    law = spec["law"]
    if law in ("uniform", "uniform_int"):
        vals = rng.uniform(lo, hi + (law == "uniform_int"), n)
    elif law in ("log_uniform", "log_uniform_int"):
        vals = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    else:
        raise ValueError(f"unknown law {law!r}")
    if law.endswith("_int"):
        return tuple(min(int(v), int(hi)) if law == "uniform_int"
                     else int(round(v)) for v in vals)
    return tuple(float(v) for v in vals)


def _axis(spec, rng) -> tuple:
    """A literal list, or ``{"draws", "law", "low", "high", "base"}``."""
    if isinstance(spec, list):
        return tuple(spec)
    out = _draw(spec, rng, int(spec["draws"]))
    return ((None,) + out) if spec.get("base") else out


@dataclass(frozen=True)
class Shape:
    """One step shape (the program's ``ShapeSpec`` fields): a decode is
    one token per sequence against ``seq_len`` cached positions."""

    name: str
    kind: str
    seq_len: int
    global_batch: int

    @property
    def key(self) -> tuple:
        return (self.kind, self.seq_len, self.global_batch)


@dataclass
class Grid:
    """One call's axes, in the program's enumeration order."""

    shapes: tuple
    chips: tuple
    stages: tuple
    links: tuple
    loss_p: tuple
    rate_scale: tuple

    @property
    def shape(self) -> tuple:
        return (len(self.shapes), len(self.chips), len(self.stages),
                len(self.links), len(self.loss_p), len(self.rate_scale))

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))

    def scenario(self, idx: int) -> tuple:
        """(shape name, mix, stages, link, loss, rate) of row ``idx``:
        shape major, then chip-group mix, stages, link, loss, rate."""
        i = np.unravel_index(idx, self.shape)
        return (self.shapes[i[0]].name, f"x{self.chips[i[1]]}",
                self.stages[i[2]], self.links[i[3]], self.loss_p[i[4]],
                self.rate_scale[i[5]])

    def shape_named(self, name: str) -> Shape:
        return next(s for s in self.shapes if s.name == name)


class Traffic:
    """The deployment plus the draws of every call, from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg = cfg
        self.spec = traffic
        self.dep = PipelineDeployment(cfg)
        g = traffic["grid"]
        self.rows_per_call = int(traffic.get("check", {}).get("rows_per_call",
                                                              ROWS_PER_CALL))
        rng = np.random.default_rng([seed, 1])
        self.grids = [self._draw(g, rng) for _ in range(MAX_CALLS + 1)]
        pick = np.random.default_rng([seed, 2])
        size = self.grids[0].size
        k = min(self.rows_per_call, size)
        self.samples = [np.sort(pick.choice(size, size=k, replace=False))
                        for _ in range(MAX_CALLS)]

    @staticmethod
    def _draw(g: dict, rng) -> Grid:
        shapes = []
        p, d = g["prefill"], g["decode"]
        for j, (b, s) in enumerate(zip(_draw(p["batch"], rng, p["count"]),
                                       _draw(p["seq_len"], rng, p["count"]))):
            shapes.append(Shape(f"prefill{j}_b{b}_s{s}", "prefill", s, b))
        for j, (b, kv) in enumerate(zip(_draw(d["batch"], rng, d["count"]),
                                        _draw(d["kv_len"], rng, d["count"]))):
            shapes.append(Shape(f"decode{j}_b{b}_kv{kv}", "decode", kv, b))
        return Grid(shapes=tuple(shapes),
                    chips=tuple(int(c) for c in g["chips_per_stage"]),
                    stages=tuple(int(n) for n in g["stages"]),
                    links=tuple(g["links"]),
                    loss_p=_axis(g["loss_p"], rng),
                    rate_scale=_axis(g["rate_scale"], rng))


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def program_objects(cfg: dict):
    """The program's model configuration and links, built from the
    deployment file's published hyper-parameters."""
    from repro.core.latency import LinkProfile
    from repro.models.config import ModelConfig

    model = ModelConfig(
        name=cfg["model_type"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        n_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        first_k_dense=cfg["first_k_dense_replace"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_mtp_modules=cfg["num_nextn_predict_layers"], use_mla=True,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        tie_embeddings=cfg["tie_word_embeddings"])
    links = {k: LinkProfile(**v) for k, v in cfg["links"].items()}
    return model, links


class Driver:
    """``setup`` (warm call), ``window`` (closed loop), ``check``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg = cfg
        self.traffic = Traffic(cfg, traffic, seed)
        self.solver = traffic.get("solver", "batched_dp")
        self.backend = traffic.get("backend", "pallas")
        self.calls: list[dict] = []

    def _grid(self, g: Grid):
        return self.pipeline_grid(
            self.model, g.shapes, g.chips, g.stages,
            {k: self.links[k] for k in g.links}, g.loss_p, g.rate_scale)

    def _sweep(self, grid):
        return self.sweep(grid, solver=self.solver, backend=self.backend)

    def setup(self) -> None:
        from repro.core.planner import pipeline_grid
        from repro.core.sweep import sweep

        self.pipeline_grid, self.sweep = pipeline_grid, sweep
        self.model, self.links = program_objects(self.cfg)
        # the warm call uses the spare last draw: same shapes as the window
        self._sweep(self._grid(self.traffic.grids[-1]))

    def window(self, seconds: float) -> tuple[float, float]:
        """Back-to-back calls while the window is open; every call that
        started finishes and counts. Returns (start, end) on the
        benchmark's clock."""
        import jax

        clock = time.perf_counter
        start = clock()
        i = len(self.calls)
        while i < MAX_CALLS:
            # t0..t1 is the sweep call, as in ``sweep_loop``; the grid
            # build before it is ``profile_s``
            with jax.profiler.TraceAnnotation("bench.pipeline_call"):
                p0 = clock()
                grid = self._grid(self.traffic.grids[i])
                t0 = clock()
                res = self._sweep(grid)
            t1 = clock()
            rows = res.rows
            kept = {}
            for idx in self.traffic.samples[i]:
                if idx < len(rows):
                    r = rows[idx]
                    sc = r.scenario
                    kept[int(idx)] = (
                        (sc.model, sc.mix, sc.n_devices, sc.protocol,
                         sc.loss_p, sc.rate_scale),
                        tuple(r.splits), bool(r.feasible), r.objective_cost_s,
                        r.total_latency_s, r.device_s, r.transmission_s)
            self.calls.append({
                "i": i, "scenarios": len(rows), "t0": t0, "t1": t1,
                "profile_s": t0 - p0, "build_s": res.build_time_s,
                "solve_s": res.solve_time_s, "kept": kept})
            del res, rows, grid
            i += 1
            if t1 - start >= seconds:
                break
        return start, self.calls[-1]["t1"]

    # -- records the metric readers use ------------------------------------
    def records(self) -> dict:
        g = self.traffic.grids[0]
        per_stages = g.size // (len(g.shapes) * len(g.chips) * len(g.stages))
        # each shape is one sweep group; its scenarios' stage counts
        fleet = [n for _ in g.chips for n in g.stages for _ in range(per_stages)]
        return {"calls": self.calls, "L": self.cfg["num_hidden_layers"] + 2,
                "groups": len(g.shapes), "group_fleet": fleet,
                "bank_matrices": 2 * len(g.chips), "grid_size": g.size}

    def attempted(self) -> tuple[int, int]:
        """(calls made, calls that returned fewer rows than the grid)."""
        size = self.traffic.grids[0].size
        return len(self.calls), sum(c["scenarios"] != size for c in self.calls)

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        for name in ("sweep", "pipeline_grid", "model", "links"):
            self.__dict__.pop(name, None)

    def check(self, limits: dict) -> dict:
        answers = [(c["i"], c["kept"]) for c in self.calls]
        return compare(self.traffic, answers, limits)


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------


def _costs(traffic: Traffic, call: int, sc: tuple, cache: dict):
    """(local, tx, link) of a scenario's stages, cached by what sets them."""
    name, mix, _, link, loss, rate = sc
    shape = traffic.grids[call].shape_named(name)
    key = (shape.key, mix, link, loss, rate)
    hit = cache.get(key)
    if hit is None:
        dep = traffic.dep
        lk = dep.link(link, loss, rate)
        hit = (dep.local(shape.key, int(mix[1:])), dep.airtime(shape.key, lk), lk)
        cache[key] = hit
    return hit


def reference(traffic: Traffic, rows) -> dict:
    """{(call, idx): (cost, cuts)}: the float64 bottleneck optimum of each
    row; one DP per distinct stage-cost matrix serves every stage count."""
    cache: dict = {}
    tables: dict = {}
    out = {}
    for call, idx in rows:
        sc = traffic.grids[call].scenario(idx)
        local, tx, _ = _costs(traffic, call, sc, cache)
        key = id(local), id(tx)
        if key not in tables:
            tables[key] = bottleneck_tables(local + tx[None, :],
                                            max(traffic.grids[call].stages))
        dps, parents = tables[key]
        n, L = sc[2], local.shape[0]
        out[(call, idx)] = (float(dps[n - 1, L - 1]), splits_from(parents, n, L))
    return out


def compare(traffic: Traffic, answers, limits: dict) -> dict:
    """Every number compared, ``{name: value}``:

    * ``missing`` - sampled rows the call did not return, or returned
      for another scenario than the grid's enumeration puts there;
    * ``feasibility`` - rows whose feasibility differs;
    * ``cost_gap`` - widest relative gap of the served bottleneck, and
      of the served total latency, from the reference optimum;
    * ``regret`` - widest relative excess of the served cuts, priced in
      float64, over the reference optimum;
    * ``rows_gap`` - widest relative gap of the served summed stage and
      shipping times from the float64 price of the served cuts."""
    wanted = [(call, int(idx)) for call, _ in answers
              for idx in traffic.samples[call]]
    ref = reference(traffic, wanted)
    kept_by = dict(answers)
    cache: dict = {}
    missing = feas = 0
    cost_gap = regret = rows_gap = 0.0
    for call, idx in wanted:
        ans = kept_by[call].get(idx)
        sc = traffic.grids[call].scenario(idx)
        if ans is None or ans[0] != sc:
            missing += 1
            continue
        _, cuts, feasible, obj, total, dev_s, tx_s = ans
        ref_cost, _ = ref[(call, idx)]
        if feasible != math.isfinite(ref_cost):
            feas += 1
            continue
        if not feasible:
            continue
        local, tx, lk = _costs(traffic, call, sc, cache)
        if len(cuts) != sc[2] - 1:
            regret = INF
            continue
        worst, d, t = price(local, tx, cuts)
        regret = max(regret, (worst - ref_cost) / ref_cost)
        ref_total = ref_cost + lk["t_setup_s"] + lk["t_feedback_s"]
        cost_gap = max(cost_gap, abs(obj - ref_cost) / ref_cost,
                       abs(total - ref_total) / ref_total)
        for got, want in ((dev_s, d), (tx_s, t)):
            rows_gap = max(rows_gap, abs(got - want) / max(abs(want), 1e-30))
    return {"missing": missing, "feasibility": feas, "cost_gap": cost_gap,
            "regret": regret, "rows_gap": rows_gap}
