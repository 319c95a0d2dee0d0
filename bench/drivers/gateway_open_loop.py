"""Open-loop served path: device sessions observing hops on a gateway.

The traffic file (``"driver": "gateway_open_loop"``) sets the fleet
(sessions, fleet sizes, solver, surface axes and backend), the observe
arrivals (Poisson at a fixed rate, sessions picked by a Zipf law), the
hop payload, and the drift bursts: at each of ``at_s`` a ``fraction``
of the sessions currently on ``protocol`` see that protocol degrade by
``factor`` and report every ``period_s`` until a surface answers their
drifted state (adoption: the first observe, from the first drifted one
on, that the session's surface answers instead of serving the stale
decision); afterwards they report at their Zipf rate, still degraded on
that protocol. Set-up compiles the programs a burst's rebuild runs
(without publishing a surface) and serves ``warm_s`` of traffic.

Each observe is timed on the benchmark's clock from its due time to the
return of its handling (``FleetGateway.pump`` one event at a time, in
arrival order), so queueing counts. A shed observe is a failed one and
counts as never answered.

Correctness, after the window: every surface family adopted in the
window (and the one built at start-up) against the reference build of
the same request, the decision served at observes sampled from the
seed against the reference lookup in the surface the session held, and
the gateway's stale-adoption audit.
"""

from __future__ import annotations

import heapq
import math
import time
from collections import deque

import numpy as np

from bench.reference.costmodel import Deployment
from bench.reference.surface import SurfaceReference, served_ok

# observes whose decision is checked (drawn from the seed)
SAMPLE_OBSERVES = 2000
# after the window, drifted sessions still waiting get this long to adopt
GRACE_S = 10.0
# the estimators' EWMA weight (the program's default), for the first
# drifted state a burst produces
ALPHA = 0.2


def family_layout(surfaces: dict) -> dict:
    """``{n: {protocol: (pts, losses, splits, chunk, latency)}}`` of a
    program surface family."""
    out = {}
    for n, surf in surfaces.items():
        out[n] = {name: (tuple(p.packet_time_s), tuple(p.loss_p), p.splits,
                         p.chunk_bytes, p.latency_s)
                  for name, p in surf.protocols.items()}
    return out


class _Phase:
    """The benchmark's host span around the current phase of the loop
    (``bench.serve.steady`` / ``bench.serve.drift``); the trace reduction
    labels the device's idle gaps with it."""

    def __init__(self, name: str):
        self._open(name)

    def _open(self, name: str) -> None:
        import jax

        self._span = jax.profiler.TraceAnnotation(name)
        self._span.__enter__()

    def switch(self, name: str) -> None:
        self.close()
        self._open(name)

    def close(self) -> None:
        self._span.__exit__(None, None, None)


def _decision(cur):
    return None if cur is None else (cur.protocol, tuple(cur.splits), int(cur.chunk_bytes))


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg = cfg
        self.spec = traffic
        self.seed = seed
        self.dep = Deployment(cfg)
        self.ref = SurfaceReference(self.dep)
        t = traffic
        self.n_sessions = int(t["sessions"])
        self.sizes = tuple(int(n) for n in t["fleet_sizes"])
        self.nbytes = int(t["hop_bytes"])
        self.rate = float(t["rate_per_s"])
        self.burst = dict(t["burst"])
        self.sample_n = int(t.get("check", {}).get("sample_observes", SAMPLE_OBSERVES))
        # nominal hop latency per protocol: the deployment's own link
        self.nominal = {name: math.ceil(self.nbytes / lk["mtu_bytes"]) * Deployment.packet_time(lk)
                        for name, lk in self.dep.protocols.items()}
        rng = np.random.default_rng([seed, 1])
        ranks = np.arange(1, self.n_sessions + 1, dtype=np.float64)
        w = ranks ** -float(t["zipf_s"])
        self.zipf_p = w / w.sum()
        self.zipf_order = rng.permutation(self.n_sessions)
        self.rng = rng
        self.pick = np.random.default_rng([seed, 2])
        self.burst_rng = np.random.default_rng([seed, 3])
        self.degraded: dict[int, set] = {}
        self.requests: dict[int, tuple] = {}
        self.families: dict[int, dict] = {}

    # -- the system under test -------------------------------------------
    def _program(self):
        from repro.core.latency import DeviceProfile, LayerCost, LinkProfile, ModelCostProfile, SplitCostModel
        from repro.runtime.gateway import FleetGateway

        m = self.cfg["model"]
        layers = tuple(LayerCost(**dict(zip(m["layer_fields"], r))) for r in m["layers"])
        profile = ModelCostProfile(name=m["name"], layers=layers,
                                   input_bytes=int(m["input_bytes"]))
        device = DeviceProfile(**self.dep.device)
        links = {k: LinkProfile(**v) for k, v in self.dep.protocols.items()}
        model = SplitCostModel(profile=profile, devices=(device,),
                               link=links[self.burst["protocol"]])
        grid = dict(self.spec["surface_grid"])
        grid = {k: tuple(v) if isinstance(v, list) else v for k, v in grid.items()}
        return FleetGateway(model, links, self.sizes, solver=self.spec["solver"],
                            surface_grid=grid,
                            max_pending=int(self.spec.get("max_pending", 100_000)),
                            fleet_window=int(self.rate * 120) + 200_000)

    def setup(self) -> None:
        self.gw = gw = self._program()
        grid = self.spec["surface_grid"]
        self.base_request = (0, tuple(grid["pt_scale"]), tuple(grid["loss_p"]), self.sizes)
        self.families[0] = family_layout(gw.surfaces)
        self.requests[0] = self.base_request
        for i in range(self.n_sessions):
            gw.register(f"s{i}", self.sizes[i % len(self.sizes)],
                        bytes_per_token=self.nbytes)
        self.sids = list(gw.sessions)
        self._gen_seen = 0
        self._warm_rebuilds()
        # the served path at the cell's rate, no drift yet
        self._loop(seconds=float(self.burst.get("warm_s", 1.0)), bursts_at=[],
                   record=False)

    def _warm_rebuilds(self) -> None:
        """Compile every program a rebuild in the window can run, through
        the rebuilder's own synchronous build; nothing is published, so
        the window's burst still waits for a real rebuild.

        A rebuild's program is fixed by its largest fleet size and its
        node count, and the node count by how many distinct drifted
        states the rebuilder merged into the request's axes (each adds
        its own ``pt_pad`` nodes). Set-up builds, for every fleet size,
        the requests that merge 1 .. ``warm_states`` of the states a
        burst's sessions pass through (the estimators' EWMA after 1, 2,
        ... drifted observes)."""
        from repro.core.async_replan import RebuildRequest, recentered_axes

        rb = self.gw.rebuilder
        proto = self.burst["protocol"]
        nominal = {name: (Deployment.packet_time(lk), lk["loss_p"])
                   for name, lk in self.dep.protocols.items()}
        pt, lp = nominal[proto]
        drifted = []
        for k in range(1, int(self.burst["warm_states"]) + 1):
            w = (1 - ALPHA) ** k
            drifted.append({**nominal, proto: (pt * (w + (1 - w) * self.burst["factor"]), lp)})
            pts, losses = recentered_axes(rb.protocols, drifted, pt_scale=rb.pt_scale,
                                          loss_p=rb.loss_p, pt_pad=rb.pt_pad,
                                          loss_pad=rb.loss_pad)
            for n in self.sizes:
                rb.build_sync(RebuildRequest(generation=0, sizes=(n,), pt_scale=pts,
                                             loss_p=losses, envelopes={}))

    # -- open loop ---------------------------------------------------------
    def _arrivals(self, seconds: float):
        n = self.rng.poisson(self.rate * seconds)
        times = np.sort(self.rng.uniform(0.0, seconds, n))
        ranks = self.rng.choice(self.n_sessions, size=n, p=self.zipf_p)
        return times, self.zipf_order[ranks]

    def _latency(self, sid: int) -> float:
        sess = self.gw.sessions[self.sids[sid]]
        proto = sess.protocol
        f = self.burst["factor"] if proto in self.degraded.get(sid, ()) else 1.0
        return self.nominal[proto] * f

    def _start_burst(self, t: float, heap: list, drifts: dict) -> None:
        proto = self.burst["protocol"]
        eligible = [i for i, sid in enumerate(self.sids)
                    if self.gw.sessions[sid].protocol == proto
                    and proto not in self.degraded.get(i, ())]
        k = min(len(eligible), int(round(self.burst["fraction"] * self.n_sessions)))
        chosen = self.burst_rng.choice(len(eligible), size=k, replace=False)
        for c in sorted(chosen):
            i = eligible[int(c)]
            self.degraded.setdefault(i, set()).add(proto)
            hits = self.gw.sessions[self.sids[i]].manager.surface_hits
            drifts[i] = {"first_due": t, "hits0": hits, "adopted": None}
            heapq.heappush(heap, (t, 1, i))

    def _capture_builds(self) -> None:
        """Record every rebuild request launched and every family
        published, by generation (checked after the window)."""
        rb = self.gw.rebuilder
        req = rb.last_request
        if req is not None and req.generation not in self.requests:
            self.requests[req.generation] = (req.generation, tuple(req.pt_scale),
                                             tuple(req.loss_p), tuple(req.sizes))
        fo = self.gw.fanout
        if fo.seq != self._gen_seen:
            self._gen_seen = fo.seq
            for n in self.sizes:
                got = fo.latest(n)
                if got is not None:
                    gen, surf = got
                    fam = self.families.setdefault(gen, {})
                    if n not in fam:
                        fam.update(family_layout({n: surf}))

    def _loop(self, seconds: float, bursts_at, record: bool):
        gw = self.gw
        clock = time.perf_counter
        times, who = self._arrivals(seconds)
        heap: list = []  # (due, kind, session) for drift reports
        drifts: dict = {}
        fifo: deque = deque()
        lat: list = []
        failed = 0
        samples: list = []
        sample_every = max(1, int(len(times) / max(self.sample_n, 1)))
        period = float(self.burst["period_s"])
        bursts = list(bursts_at)
        nxt = 0
        seq = 0
        waiting = 0  # drifted sessions not yet adopted
        start = clock()
        req0, started0 = gw.rebuilder.requests, gw.rebuilder.builds_started
        qcount0 = gw.qos.global_window.count
        phase = _Phase("bench.serve.steady")
        while True:
            now = clock() - start
            while bursts and bursts[0] <= now:
                self._start_burst(bursts.pop(0), heap, drifts)
                waiting = sum(1 for d in drifts.values() if d["adopted"] is None)
                phase.switch("bench.serve.drift")
            # admit every observe due by now, in due order
            while True:
                t_arr = times[nxt] if nxt < len(times) else math.inf
                t_drift = heap[0][0] if heap else math.inf
                due = min(t_arr, t_drift)
                if due > now:
                    break
                if t_drift <= t_arr:
                    _, _, i = heapq.heappop(heap)
                    if drifts[i]["adopted"] is not None:
                        continue
                    heapq.heappush(heap, (due + period, 1, i))
                else:
                    i = int(who[nxt])
                    nxt += 1
                sid = self.sids[i]
                if gw.submit_observe(sid, self.nbytes, self._latency(i)):
                    fifo.append((due, i, seq))
                else:
                    failed += 1
                    lat.append((due, None))
                seq += 1
            if fifo:
                due, i, s = fifo.popleft()
                sess = gw.sessions[self.sids[i]]
                check = record and s % sample_every == 0 and len(samples) < self.sample_n
                prev = _decision(sess.manager.current) if check else None
                gw.pump(1)
                done = clock() - start
                lat.append((due, done))
                d = drifts.get(i)
                if d is not None and d["adopted"] is None and due >= d["first_due"] \
                        and sess.manager.surface_hits > d["hits0"]:
                    # the surface answered the drifted state: stale
                    # serving has ended
                    d["adopted"] = done
                    waiting -= 1
                    if waiting == 0:
                        phase.switch("bench.serve.steady")
                if check:
                    m = sess.manager
                    states = {k: (e.packet_time_estimate, e.loss_estimate)
                              for k, e in m.estimators.items()}
                    samples.append((id(m.surface), m.surface, states, prev,
                                    _decision(m.current), sess.n_devices))
                self._capture_builds()
                continue
            pending = [d for d in drifts.values() if d["adopted"] is None]
            if nxt >= len(times) and not bursts and (not pending or now > seconds + GRACE_S):
                break
            wait = min(times[nxt] if nxt < len(times) else math.inf,
                       heap[0][0] if heap else math.inf,
                       bursts[0] if bursts else math.inf) - (clock() - start)
            if wait > 0:
                time.sleep(wait)
        phase.close()
        self.last = {
            "seconds": seconds,
            "latencies": lat, "failed": failed, "drifts": drifts,
            "samples": samples, "offered": len(times),
            "requests": gw.rebuilder.requests - req0,
            "builds": gw.rebuilder.builds_started - started0,
            "qos_range": (qcount0, gw.qos.global_window.count),
        }
        return start, start + seconds

    def window(self, seconds: float) -> tuple[float, float]:
        bursts = [float(t) for t in self.burst["at_s"] if t < seconds]
        # the window is the offered span; what finished later was waited for
        return self._loop(seconds, bursts, record=True)

    # -- records -------------------------------------------------------------
    def records(self) -> dict:
        L = self.last
        lo, hi = L["qos_range"]
        handled = self.gw.qos.global_window.values()[lo:hi]
        return {**{k: L[k] for k in ("latencies", "failed", "drifts", "offered",
                                     "requests", "builds", "seconds")},
                "handle_s": handled}

    def attempted(self) -> tuple[int, int]:
        lat = [x for x in self.last["latencies"] if x[0] < self.last["seconds"]]
        return len(lat), sum(1 for _, done in lat if done is None)

    def free(self) -> None:
        snap = self.gw.snapshot()
        self.stale = snap.counters["stale_adoption_violations"]
        self.rebuild_errors = self.gw.rebuild_errors
        self.gw.close()
        del self.gw

    def check(self, limits: dict) -> dict:
        out = {"surf_missing": 0, "surf_feasibility": 0, "surf_regret": 0.0,
               "surf_latency_gap": 0.0, "surf_chunk": 0}
        for gen, fam in self.families.items():
            req = self.requests.get(gen)
            if req is None:
                out["surf_missing"] += 1
                continue
            _, pt_scale, loss_p, sizes = req
            got = self.ref.compare(fam, pt_scale, loss_p, [n for n in sizes if n in fam])
            for k, v in got.items():
                out[k] = max(out[k], v) if isinstance(v, float) else out[k] + v
        bad = 0
        layouts = {}
        for key, surf, states, prev, served, n in self.last["samples"]:
            fam = layouts.get(key)
            if fam is None:
                fam = layouts[key] = family_layout({n: surf})[n]
            bad += not served_ok(self.ref, fam, states, prev, served)
        out["decisions"] = bad
        out["stale_adoptions"] = self.stale
        out["rebuild_errors"] = self.rebuild_errors
        return out
