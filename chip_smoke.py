#!/usr/bin/env python3
"""Chip smoke test: the split planner's device path, end to end, on a TPU.

Runs the planner's normal entry points on the chip at the paper's full
model depths (MobileNetV2 L=54, ResNet50 L=52, fleets of 2-5 ESP32-S3
devices, all four protocols) and checks every result against the float64
NumPy oracle:

  2. ``PlannerService.solve`` on a stacked ``C[S, 5, L, L]`` per model
     (S=16,384) with ``backend="jax"`` and ``backend="pallas"`` (dense);
     dense Pallas must be bit-identical to jax, tables and parents;
  3. ``sweep(grid, backend="pallas")``, the fused kernel that never
     builds ``C``;
  4. the Pallas kernels compiled through Mosaic (no interpret mode);
  5. a ``FleetGateway`` whose surface family and drift rebuilds are DP
     solves on the device;
  6. MobileNetV2 at full width, split at the phase-2 plan, against the
     unsplit forward pass.

``--four-chips`` runs only the sharded DP (``backend="sharded"``) on a
four-chip mesh against ``backend="jax"`` on one device.

Usage, from the repository root on a machine with a TPU:

  python chip_smoke.py
  python chip_smoke.py --four-chips

With no TPU it exits non-zero before any planning and prints no result.
A failed check exits non-zero. The last line of standard output is
``{"ok": true, "device": {...}}``. Printed seconds are smoke timings (first
call includes compilation), not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PAPER_MODELS = ("mobilenet_v2", "resnet50")
# the loss and rate axes of benchmarks/sweep_grid.py are refined to
# 32 x 32 values: 4 protocols x 4 fleet sizes x 1024 = 16,384 per model
LOSS_POINTS = RATE_POINTS = 32
# float32 device costs against the float64 oracle
F32_RTOL = 1e-5
# a split that differs from the oracle's must cost the same when repriced
# in float64: an exact tie (the bound only absorbs summation order)
TIE_RTOL = 1e-12
# split execution ships the float carry unchanged, so split and unsplit
# logits should agree to float32 rounding of the same ops
LOGIT_RTOL = 1e-5
GATEWAY_SESSIONS = 1000
GATEWAY_SIZES = (2, 3, 5)
ADOPTION_TIMEOUT_S = 300.0


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def tpu_devices():
    """The TPU devices, or exit non-zero naming what JAX found instead."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind}, "
                 f"{len(devs)} device(s)); there is no CPU fallback")
    return devs


def log(msg: str) -> None:
    print(msg, flush=True)


def twice(fn):
    """Run ``fn`` twice: (result of the second call, first s, second s).
    The first call pays tracing and compilation."""
    t0 = time.perf_counter()
    fn()
    t1 = time.perf_counter()
    out = fn()
    return out, t1 - t0, time.perf_counter() - t1


# ---------------------------------------------------------------------------
# The paper's deployment grid
# ---------------------------------------------------------------------------


def paper_axes(loss_points: int = LOSS_POINTS, rate_points: int = RATE_POINTS):
    """The loss and rate-scale axes of ``benchmarks/sweep_grid.py``,
    refined over the same ranges (base loss kept)."""
    import numpy as np

    from benchmarks.sweep_grid import LOSS_P, RATE_SCALE

    losses = [p for p in LOSS_P if p is not None]
    loss = (None,) + tuple(float(x) for x in np.linspace(
        min(losses), max(losses), loss_points - 1))
    rate = tuple(float(x) for x in np.geomspace(
        max(RATE_SCALE), min(RATE_SCALE), rate_points))
    return loss, rate


def paper_grid(models, loss, rate):
    from benchmarks.sweep_grid import DEVICES
    from repro.core.profiles import ESP32, PROTOCOLS, paper_cost_model
    from repro.core.sweep import ScenarioGrid

    return ScenarioGrid(
        models={m: paper_cost_model(m).profile for m in models},
        links=dict(PROTOCOLS), n_devices=DEVICES, loss_p=loss,
        rate_scale=rate, devices=(ESP32,))


def stacked_tensor(grid):
    """(scenarios, C[S, 5, L, L] float64, per-scenario fleet sizes)."""
    import numpy as np

    from repro.core.sweep import stack_cost_tensors

    scs = grid.scenarios()
    ns = np.array([sc.n_devices for sc in scs], dtype=np.int64)
    C = stack_cost_tensors([grid.cost_model(sc) for sc in scs], ns.tolist())
    return scs, C, ns


def reprice(C, splits, ns):
    """float64 cost of each row's own splits, summed left to right over
    its live segments (the scalar oracle's order)."""
    import numpy as np

    S, N, L, _ = C.shape
    rows = np.arange(S)
    total = np.zeros(S)
    start = np.zeros(S, dtype=np.int64)
    for k in range(N):
        col = splits[:, k] if k < splits.shape[1] else np.full(S, L)
        end = np.where(k < ns - 1, col, L)
        # dead slots (k >= ns) read a clipped dummy entry, then drop it
        seg = C[rows, k, np.minimum(start, L - 1), np.clip(end - 1, 0, L - 1)]
        total = np.where(k < ns, total + seg, total)
        start = end
    return total


def check_vs_oracle(label, res, oracle, C, ns):
    """Feasibility equal, costs allclose, and every split that differs
    from the oracle's an exact tie. Returns (differing, max regret)."""
    import numpy as np

    check(np.array_equal(res.feasible, oracle.feasible),
          f"{label}: feasibility differs from the numpy oracle")
    fin = oracle.feasible
    check(np.allclose(res.cost_s[fin], oracle.cost_s[fin], rtol=F32_RTOL,
                      atol=0.0),
          f"{label}: costs not allclose to the numpy oracle")
    differ = np.flatnonzero(fin & np.any(res.splits != oracle.splits, axis=1))
    regret = 0.0
    if differ.size:
        got = reprice(C[differ], res.splits[differ], ns[differ])
        rel = (got - oracle.cost_s[differ]) / oracle.cost_s[differ]
        regret = float(rel.max())
    check(regret <= TIE_RTOL,
          f"{label}: a split differing from the oracle has float64 regret "
          f"{regret:.3e} (> {TIE_RTOL:g}): not a tie")
    return int(differ.size), regret


def dense_rows(S: int, N: int) -> int:
    """Scenarios dense Pallas takes: all of them if the host can hold the
    lane-padded float32 tensor twice over, else the first 8,192."""
    from repro.core.pallas_dp import LANE

    need = 2 * S * N * LANE * LANE * 4
    avail = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    return S if need <= avail else min(S, 8192)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_plan_on_device(loss, rate):
    """Phase 2: spec-resolved solves of the stacked tensor per model."""
    import numpy as np

    from repro.core.spec import PlannerService, tensor_spec
    from repro.core.sweep import DP_BACKENDS

    service = PlannerService()
    plans = {}
    shapes = {}
    for model in PAPER_MODELS:
        grid = paper_grid((model,), loss, rate)
        t0 = time.perf_counter()
        scs, C, ns = stacked_tensor(grid)
        build_s = time.perf_counter() - t0
        S, N, L, _ = C.shape
        log(f"[2] {model}: C{list(C.shape)} float64 "
            f"({C.size * 4 / 1e9:.3f} GB as float32), host build "
            f"{build_s:.2f}s")

        t0 = time.perf_counter()
        oracle = service.solve(tensor_spec(C, n_devices=ns), C)
        log(f"[2] {model}: numpy oracle {time.perf_counter() - t0:.2f}s, "
            f"{int(oracle.feasible.sum())}/{S} feasible")

        def solve(backend, C=C, ns=ns):
            return service.solve(
                tensor_spec(C, backend=backend, n_devices=ns), C)

        jax_res, first, second = twice(lambda: solve("jax"))
        n_diff, regret = check_vs_oracle(f"{model} jax", jax_res, oracle,
                                         C, ns)
        log(f"[2] {model}: jax first {first:.2f}s second {second:.2f}s; "
            f"{n_diff} splits differ from the oracle, all ties "
            f"(max regret {regret:.1e})")

        Sd = dense_rows(S, N)
        Cd, nsd = C[:Sd], ns[:Sd]
        sub = replace(oracle, splits=oracle.splits[:Sd],
                      cost_s=oracle.cost_s[:Sd],
                      feasible=oracle.feasible[:Sd],
                      n_devices_s=oracle.n_devices_s[:Sd])
        pal_res, first, second = twice(
            lambda: solve("pallas", C=Cd, ns=nsd))
        n_diff, regret = check_vs_oracle(f"{model} pallas", pal_res, sub,
                                         Cd, nsd)
        log(f"[2] {model}: dense pallas on S={Sd} (Lp=128) first "
            f"{first:.2f}s second {second:.2f}s; {n_diff} splits differ "
            f"from the oracle, all ties (max regret {regret:.1e})")

        # dense pallas reorders no arithmetic: bit-identical to jax
        dj, pj = DP_BACKENDS["jax"](Cd, "sum", nsd)
        dp, pp = DP_BACKENDS["pallas"](Cd, "sum", nsd)
        check(all(np.array_equal(a, b) for a, b in zip(dj, dp)),
              f"{model}: dense pallas DP tables differ from jax")
        check(np.array_equal(pj, pp),
              f"{model}: dense pallas parents differ from jax")
        log(f"[2] {model}: dense pallas tables and parents bit-identical "
            f"to jax ({len(dj)} tables of {list(dj[0].shape)})")
        plans[model] = (scs, pal_res)
        shapes[model] = (Sd, N)
        del C, Cd
    return plans, shapes


def phase_fused_sweep(loss, rate):
    """Phase 3: the fused kernel through ``sweep``, against numpy."""
    import numpy as np

    from repro.core import solvers as S
    from repro.core.sweep import sweep

    grid = paper_grid(PAPER_MODELS, loss, rate)
    t0 = time.perf_counter()
    ref = sweep(grid)
    log(f"[3] sweep grid {grid.size} scenarios ({', '.join(PAPER_MODELS)}):"
        f" numpy oracle {time.perf_counter() - t0:.2f}s")
    fused, first, second = twice(lambda: sweep(grid, backend="pallas"))
    n_diff, regret = 0, 0.0
    for a, b in zip(ref.rows, fused.rows):
        check(a.feasible == b.feasible,
              f"fused sweep: feasibility differs at {a.scenario.describe()}")
        if not a.feasible:
            continue
        check(np.isclose(b.objective_cost_s, a.objective_cost_s,
                         rtol=F32_RTOL, atol=0.0),
              f"fused sweep: cost not allclose at {a.scenario.describe()}")
        if a.splits != b.splits:
            n_diff += 1
            m = grid.cost_model(b.scenario)
            got = S.total_cost(m.cost_segment_fn(), b.splits,
                               m.profile.num_layers, "sum")
            regret = max(regret, (got - a.objective_cost_s)
                         / a.objective_cost_s)
    check(regret <= TIE_RTOL,
          f"fused sweep: a differing split has float64 regret "
          f"{regret:.3e}: not a tie")
    log(f"[3] fused pallas sweep first {first:.2f}s second {second:.2f}s "
        f"(build {fused.build_time_s:.2f}s + solve {fused.solve_time_s:.2f}s);"
        f" {n_diff} splits differ from the oracle, all ties (max regret "
        f"{regret:.1e})")
    return grid


def phase_compiled(dense_shape, fused_rows):
    """Phase 4: interpret mode is off and the solvers that ran hold a
    Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core import pallas_dp as PD

    interpret = PD.pallas_interpret_default()
    log(f"[4] pallas_interpret_default() = {interpret}")
    check(interpret is False, "pallas runs in interpret mode on the chip")
    bs, lp = PD.DEFAULT_BLOCK_S, PD.LANE
    Sd, N = dense_shape
    Sp = PD._pad_rows(Sd, bs)
    Sf = PD._pad_rows(fused_rows, bs)
    f32, i32 = jnp.float32, jnp.int32
    programs = {
        "dense": (((Sp, N, lp, lp), f32), ((Sp, 1), i32)),
        "fused": (((N, lp, lp), f32), ((Sf, lp), f32), ((Sf, 1), i32)),
    }
    for mode, operands in programs.items():
        args = [jax.ShapeDtypeStruct(s, dt) for s, dt in operands]
        text = PD._pallas_dp_solver(mode, "sum", bs, False).lower(
            *args).compile().as_text()
        found = "tpu_custom_call" in text
        log(f"[4] {mode} pallas solver at {[list(s) for s, _ in operands]}:"
            f" tpu_custom_call in compiled text = {found}")
        check(found, f"{mode} pallas solver holds no Mosaic kernel")


def compare_surfaces(label, got, ref, cost_model):
    """Node decisions equal, or an exact tie at that node's link."""
    import numpy as np

    from repro.core import solvers as S
    from repro.core.surface import refit_link

    L = cost_model.profile.num_layers
    nodes = ties = 0
    for name, ps in got.protocols.items():
        pr = ref.protocols[name]
        check(ps.packet_time_s == pr.packet_time_s
              and ps.loss_p == pr.loss_p, f"{label}/{name}: axes differ")
        feas = np.isfinite(ps.latency_s)
        check(np.array_equal(feas, np.isfinite(pr.latency_s)),
              f"{label}/{name}: node feasibility differs")
        same = np.all(ps.splits == pr.splits, axis=-1)
        check(np.array_equal(ps.latency_s[same], pr.latency_s[same]),
              f"{label}/{name}: latency differs where splits agree")
        nodes += ps.latency_s.size
        for i, j in zip(*np.nonzero(feas & ~same)):
            link = refit_link(ps.base, ps.packet_time_s[i], ps.loss_p[j])
            fn = replace(cost_model, link=link).cost_segment_fn()
            a = S.total_cost(fn, tuple(ps.splits[i, j]), L)
            b = S.total_cost(fn, tuple(pr.splits[i, j]), L)
            check(abs(a - b) <= TIE_RTOL * b,
                  f"{label}/{name}: node ({i},{j}) differs beyond a tie")
            ties += 1
    return nodes, ties


def phase_gateway():
    """Phase 5: a gateway whose family and rebuilds run on the device."""
    from benchmarks.gateway_load import GRID, NBYTES, STORM_FACTOR
    from repro.core.profiles import PROTOCOLS, paper_cost_model
    from repro.core.spec import PlannerService
    from repro.runtime.gateway import FleetGateway

    model = paper_cost_model("mobilenet_v2", "esp_now")
    service = PlannerService()
    t0 = time.perf_counter()
    gw = FleetGateway(model, PROTOCOLS, GATEWAY_SIZES, solver="optimal_dp",
                      surface_grid={**GRID, "backend": "pallas"},
                      max_pending=4 * GATEWAY_SESSIONS)
    try:
        build_s = time.perf_counter() - t0
        spec = gw.plan_spec
        check(spec.solver == "batched_dp" and spec.backend == "pallas",
              f"gateway family spec is {spec.solver}/{spec.backend}")
        log(f"[5] gateway family {spec.solver}/{spec.backend} for sizes "
            f"{GATEWAY_SIZES} built in {build_s:.2f}s")
        ref = service.build_surfaces(replace(spec, backend="numpy"))
        for n in GATEWAY_SIZES:
            nodes, ties = compare_surfaces(f"family n={n}", gw.surfaces[n],
                                           ref[n], model)
            log(f"[5] family n={n}: {nodes} nodes equal the numpy build "
                f"({ties} exact ties)")

        for i in range(GATEWAY_SESSIONS):
            gw.register(f"s{i}", GATEWAY_SIZES[i % len(GATEWAY_SIZES)],
                        bytes_per_token=NBYTES)
        sids = list(gw.sessions)

        def nominal(sid):
            return gw.sessions[sid].meter.link.transmission_latency_s(NBYTES)

        for sid in sids:
            gw.submit_observe(sid, NBYTES, nominal(sid))
        gw.pump()
        drifted = sids[-GATEWAY_SESSIONS // 10:]
        t0 = time.perf_counter()
        remaining = list(drifted)
        while remaining and time.perf_counter() - t0 < ADOPTION_TIMEOUT_S:
            for sid in remaining:
                gw.submit_observe(sid, NBYTES, nominal(sid) * STORM_FACTOR)
            gw.pump()
            remaining = [s for s in remaining
                         if gw.sessions[s].manager.surface_swaps == 0]
            if remaining:
                time.sleep(0.01)
        wait_s = time.perf_counter() - t0
        check(not remaining,
              f"{len(remaining)} drifted sessions never adopted a rebuild")
        # settle: no build in flight and the newest build published (a
        # snapshot may launch a queued follow-up, so poll until both hold)
        while True:
            snap = gw.snapshot()
            req = gw.rebuilder.last_request
            if gw.rebuilder.inflight() is None and all(
                    gw.fanout.latest(n)[0] == req.generation
                    for n in req.sizes):
                break
            check(time.perf_counter() - t0 < ADOPTION_TIMEOUT_S,
                  "rebuilds did not settle")
            time.sleep(0.01)
        c = snap.counters
        check(c["stale_adoption_violations"] == 0, "stale adoption")
        check(gw.rebuild_errors == 0, "a rebuild failed")
        rspec = gw.rebuilder.spec_for(req)
        check(rspec.backend == "pallas", f"rebuild ran on {rspec.backend}")
        log(f"[5] {len(sids)} sessions, {len(drifted)} drifted x"
            f"{STORM_FACTOR:g}: {c['rebuilder_requests']} rebuild requests "
            f"-> {c['builds_completed']} {rspec.backend} builds, all "
            f"drifted adopted in {wait_s:.2f}s, "
            f"{c['stale_adoption_violations']} stale adoptions")
        ref = service.build_surfaces(replace(rspec, backend="numpy"))
        for n in req.sizes:
            gen, surf = gw.fanout.latest(n)
            nodes, ties = compare_surfaces(f"rebuild n={n}", surf, ref[n],
                                           model)
            log(f"[5] rebuilt n={n} (generation {gen}): {nodes} nodes equal "
                f"the numpy build ({ties} exact ties)")
    finally:
        gw.close()


def phase_execute(plans, seed: int = 0):
    """Phase 6: full-width MobileNetV2 split at the N=3 ESP-NOW plan."""
    import jax
    import jax.numpy as jnp

    from repro.core.executor import run_split, run_unsplit
    from repro.core.profiles import PROTOCOLS
    from repro.models.mobilenetv2 import MobileNetV2

    scs, res = plans["mobilenet_v2"]
    idx = next(i for i, sc in enumerate(scs)
               if sc.protocol == "esp_now" and sc.n_devices == 3
               and sc.loss_p is None and sc.rate_scale == 1.0)
    splits = res.splits_tuple(idx)
    check(len(splits) == 2, f"no feasible N=3 ESP-NOW plan: {splits}")
    model = MobileNetV2(width=1.0, image_size=224)
    params = model.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), model.input_shape(8))
    ref, first, second = twice(
        lambda: run_unsplit(model, params, x)["h"].block_until_ready())
    t0 = time.perf_counter()
    out, trace = run_split(model, params, x, splits,
                           link=PROTOCOLS["esp_now"], quantize_wire=False)
    out = out["h"].block_until_ready()
    split_s = time.perf_counter() - t0
    diff = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    top1 = bool(jnp.all(jnp.argmax(out, -1) == jnp.argmax(ref, -1)))
    log(f"[6] MobileNetV2 width 1.0 @224, batch 8, split {splits} "
        f"({[h.boundary_layer for h in trace.hops]}): logits "
        f"{list(out.shape)}, max |split - unsplit| = {diff:.3e} "
        f"(max |logit| {scale:.3e}, tolerance {LOGIT_RTOL:g} x that), "
        f"top-1 equal: {top1}; unsplit first {first:.2f}s second "
        f"{second:.2f}s, split {split_s:.2f}s")
    check(bool(jnp.all(jnp.isfinite(out))), "non-finite logits")
    check(top1, "split top-1 differs from unsplit")
    check(diff <= LOGIT_RTOL * scale, "split logits beyond tolerance")


def phase_four_chips(devs, loss, rate):
    """The sharded DP on a four-chip mesh, against one device."""
    import numpy as np

    from repro.core.shard import sharded_optimal_dp
    from repro.core.sweep import batched_optimal_dp

    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    _, C, ns = stacked_tensor(paper_grid(("mobilenet_v2",), loss, rate))
    C, ns = C[:-3], ns[:-3]  # S not divisible by the shard count
    S = C.shape[0]
    log(f"[4c] C{list(C.shape)}, S % 4 = {S % 4}")
    sharded, first, second = twice(
        lambda: sharded_optimal_dp(C, n_devices=ns, n_shards=4))
    log(f"[4c] sharded: first {first:.2f}s second {second:.2f}s")
    # every device must have held its quarter of C (float32)
    quarter = C.size * 4 // 4
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs[:4]]
    log(f"[4c] peak bytes in use per device: {peaks} (a quarter of C in "
        f"float32 is {quarter})")
    check(all(p >= 0.9 * quarter for p in peaks),
          "a device of the mesh held no shard of C")
    one, first, second = twice(
        lambda: batched_optimal_dp(C, backend="jax", n_devices=ns))
    log(f"[4c] one-device jax: first {first:.2f}s second {second:.2f}s")
    same = (np.array_equal(sharded.splits, one.splits)
            and np.array_equal(sharded.cost_s, one.cost_s)
            and np.array_equal(sharded.feasible, one.feasible))
    log(f"[4c] sharded node-identical to one-device jax: {same}")
    check(same, "sharded differs from one-device jax")


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded DP on a four-chip mesh")
    args = ap.parse_args()

    devs = tpu_devices()  # before any planning: no CPU fallback
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    loss, rate = paper_axes()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(devs, loss, rate)
    else:
        plans, shapes = phase_plan_on_device(loss, rate)
        grid = phase_fused_sweep(loss, rate)
        fused_rows = grid.size // len(PAPER_MODELS) // 4  # one fleet size
        phase_compiled(shapes["mobilenet_v2"], fused_rows)
        phase_gateway()
        phase_execute(plans)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s "
        f"(smoke timings, not benchmark numbers)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
