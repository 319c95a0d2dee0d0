"""Spans and counters of the planner's hot path, read back from a real
profiler trace.

Each test records a JAX profiler trace of a small ``sweep`` (the Pallas
kernel in interpret mode here) into ``tmp_path`` and reads the
``.xplane.pb`` with ``ProfileData``: the ``repro.*`` span names, their
nesting by containment on one thread, and the counts they carry, checked
against values computed by hand from the shapes.
"""

from __future__ import annotations

import dataclasses
import glob
import time
from dataclasses import dataclass

import numpy as np
import pytest

from repro.core.async_replan import ManualExecutor, SurfaceRebuilder
from repro.core.profiles import (ESP32, PROTOCOLS, mobilenet_cost_profile,
                                 paper_cost_model, resnet50_cost_profile)
from repro.core.spans import SPANS
from repro.core.sweep import ScenarioGrid, sweep

F32 = I32 = 4  # bytes of the device dtypes (JAX's float32 default)

# every span's parent: the innermost span around it on its thread
PARENT = {
    "repro.sweep.enumerate": "repro.sweep",
    "repro.sweep.build": "repro.sweep",
    "repro.sweep.bank": "repro.sweep.build",
    "repro.sweep.tx": "repro.sweep.build",
    "repro.sweep.gather": "repro.sweep.build",
    "repro.sweep.rows": "repro.sweep",
    "repro.dp": "repro.sweep",
    "repro.dp.launch": "repro.dp",
    "repro.dp.prepare": "repro.dp.launch",
    "repro.dp.fetch": "repro.dp.launch",
    "repro.dp.reconstruct": "repro.dp",
}


@dataclass
class Span:
    name: str
    start: int
    end: int
    thread: tuple
    stats: dict


def traced(tmp_path, fn):
    """``fn()`` under the JAX profiler: (its value, the ``repro.*`` spans)."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = int(ev.start_ns)
                    spans.append(Span(ev.name, s, s + int(ev.duration_ns),
                                      (plane.name, t), dict(ev.stats)))
    return out, sorted(spans, key=lambda sp: (sp.start, -sp.end))


def parent(span, spans):
    """The innermost other span that contains ``span`` on its thread."""
    around = [o for o in spans if o is not span and o.thread == span.thread
              and o.start <= span.start and span.end <= o.end]
    return min(around, key=lambda o: o.end - o.start) if around else None


def named(spans, name):
    return [sp for sp in spans if sp.name == name]


def r50_grid(losses=(None, 0.05, 0.1)) -> ScenarioGrid:
    """ResNet50 (L = 52) on fleets of 2-5 ESP32s over the four links:
    four device stacks of 4 x len(losses) scenarios each."""
    return ScenarioGrid(models={"r50": resnet50_cost_profile()},
                        links=dict(PROTOCOLS), n_devices=(2, 3, 4, 5),
                        loss_p=losses, devices=(ESP32,))


# stacks: the DP's device stacks with dead slots read as bank row 0, which
# the pallas backend joins into one live stack; jax solves one dense C
@pytest.mark.parametrize("backend,stacks", [("pallas", 4), ("jax", 1),
                                            ("numpy", 0)])
def test_sweep_span_tree(tmp_path, backend, stacks):
    launches = 1 if backend == "pallas" else stacks
    grid = r50_grid()
    sweep(grid, backend=backend)  # compile outside the trace
    res, spans = traced(tmp_path, lambda: sweep(grid, backend=backend))
    assert {sp.name for sp in spans} <= set(SPANS)
    for sp in spans:
        up = parent(sp, spans)
        assert (up and up.name) == PARENT.get(sp.name), sp.name
    (call,) = named(spans, "repro.sweep")
    assert call.stats == {"scenarios": grid.size}
    for name in ("repro.sweep.enumerate", "repro.sweep.build",
                 "repro.sweep.bank", "repro.sweep.tx", "repro.dp"):
        assert len(named(spans, name)) == 1, name
    # the one group's row loop, then the final ordering
    assert len(named(spans, "repro.sweep.rows")) == 2
    # the fused path never gathers C; no group carries a budget
    gathers = named(spans, "repro.sweep.gather")
    assert len(gathers) == (backend != "pallas")
    assert all(sp.stats["budgeted"] == sp.stats["masked"] == 0
               for sp in gathers)
    # one launch for the one joined device stack on pallas, one on jax
    assert len(named(spans, "repro.dp.launch")) == launches
    (dp,) = named(spans, "repro.dp")
    if backend == "pallas":
        # every row's cost and splits were selected on the device
        assert dp.stats == {"stacks": stacks, "launches": launches,
                            "walked": grid.size}
    else:
        assert "walked" not in dp.stats
    for name in ("repro.dp.prepare", "repro.dp.fetch"):
        assert len(named(spans, name)) == launches
    assert named(spans, "repro.dp.reconstruct")
    assert res.n_scenarios == grid.size


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_budgeted_build_is_one_gather_a_group(tmp_path, backend):
    """A budgeted group's cost tensor, energy mask included, is built in
    one ``repro.sweep.gather`` that counts its blocks, workers, budgeted
    rows and masked entries; there is no separate energy span."""
    from repro.core import sweep as SW

    esp = dataclasses.replace(ESP32, active_power_w=0.5)
    links = {k: dataclasses.replace(v, tx_power_w=0.24, rx_power_w=0.12)
             for k, v in PROTOCOLS.items()}
    grid = ScenarioGrid(
        models={"r50": resnet50_cost_profile(),
                "mnv2": mobilenet_cost_profile()},
        links=links, n_devices=(2, 3, 4, 5), devices=(esp,),
        energy_budgets=(None, 30.0, 3.0))
    sweep(grid, backend=backend)
    res, spans = traced(tmp_path, lambda: sweep(grid, backend=backend))
    assert {sp.name for sp in spans} <= set(SPANS)
    assert "repro.sweep.energy" not in SPANS
    builds = named(spans, "repro.sweep.build")
    gathers = named(spans, "repro.sweep.gather")
    assert len(builds) == len(gathers) == len(grid.models) == 2
    for build, gather, profile in zip(builds, gathers, grid.models.values()):
        assert parent(gather, spans) is build
        per_group = grid.size // 2
        bs = SW._BLOCK_BYTES // (5 * profile.num_layers ** 2 * 8)
        blocks = -(-per_group // bs)
        stats = gather.stats
        assert sorted(stats) == ["blocks", "budgeted", "masked", "workers"]
        assert stats["blocks"] == blocks
        assert stats["workers"] == min(SW._CORES, blocks)
        assert stats["budgeted"] == per_group * 2 // 3
        assert 0 < stats["masked"] < stats["budgeted"] * 5 * \
            profile.num_layers ** 2
    assert res.n_scenarios == grid.size


def _pallas_launch(rows):
    N, Lp, Sp = 5, 128, -(-rows // 8) * 8  # block_s 8
    return {"kernel": "solve_fused_walk", "rows": rows, "rows_padded": Sp,
            "lanes": 52, "lanes_padded": Lp,
            # local (N, Lp, Lp), tx (Sp, Lp), ns (Sp, 1)
            "h2d_bytes": N * Lp * Lp * F32 + Sp * Lp * F32 + Sp * I32,
            # cost (Sp,) and splits (Sp, N - 1): the tables stay on the chip
            "d2h_bytes": Sp * F32 + Sp * (N - 1) * I32}


def _scan_launch(rows):
    N, L = 5, 52
    return {"kernel": "solve_scan", "rows": rows, "rows_padded": rows,
            "lanes": L, "lanes_padded": L,
            # C (S, N, L, L), ns (S,)
            "h2d_bytes": rows * N * L * L * F32 + rows * I32,
            "d2h_bytes": rows * L * F32 + 2 * rows * (N - 1) * L * F32}


@pytest.mark.parametrize("backend,launches", [
    # one launch for the one stack, 48 rows; C is never built
    ("pallas", [_pallas_launch(48)]),
    # one launch over the C (S, N, L, L) gathered on the host
    ("jax", [_scan_launch(48)]),
])
def test_launch_counters_match_the_shapes(tmp_path, backend, launches):
    grid = r50_grid()
    sweep(grid, backend=backend)
    _, spans = traced(tmp_path, lambda: sweep(grid, backend=backend))
    assert [sp.stats for sp in named(spans, "repro.dp.launch")] == launches


def test_spans_agree_with_the_timing_fields(tmp_path):
    grid = r50_grid()
    sweep(grid, backend="jax")
    res, spans = traced(tmp_path, lambda: sweep(grid, backend="jax"))
    (call,) = named(spans, "repro.sweep")
    (build,) = named(spans, "repro.sweep.build")
    (dp,) = named(spans, "repro.dp")

    def seconds(sp):
        return (sp.end - sp.start) / 1e9

    # each field is stamped just inside (wall, solve) or just outside
    # (build) its span, on another clock
    for outer, inner in ((seconds(call), res.wall_time_s),
                         (seconds(dp), res.solve_time_s),
                         (res.build_time_s, seconds(build))):
        assert -1e-5 < outer - inner < 0.01
    assert res.wall_time_s >= res.build_time_s + res.solve_time_s
    assert res.scenarios_per_sec == res.n_scenarios / res.wall_time_s


def test_span_count_does_not_grow_with_the_grid(tmp_path):
    small, large = r50_grid((None,)), r50_grid(tuple(np.linspace(0, 0.1, 9)))
    for g in (small, large):
        sweep(g, backend="pallas")
    _, a = traced(tmp_path / "a", lambda: sweep(small, backend="pallas"))
    _, b = traced(tmp_path / "b", lambda: sweep(large, backend="pallas"))
    assert [sp.name for sp in a] == [sp.name for sp in b]
    assert len(a) <= 25


def _lowered_text(name):
    import jax
    import jax.numpy as jnp

    from repro.core import pallas_dp as PD
    from repro.core import sweep as SW

    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    if name == "solve_fused":
        fn = PD._pallas_dp_solver("fused", "sum", 8, True)
        args = (S((5, 128, 128), f32), S((16, 128), f32), S((16, 1), i32))
    elif name == "solve_fused_walk":
        fn = PD._pallas_walk_solver("sum", 8, True, 52)
        args = (S((5, 128, 128), f32), S((16, 128), f32), S((16, 1), i32))
    elif name == "solve_dense":
        fn = PD._pallas_dp_solver("dense", "sum", 8, True)
        args = (S((16, 5, 128, 128), f32), S((16, 1), i32))
    else:
        fn = SW._dp_jax_solver("sum")
        args = (S((16, 5, 52, 52), f32), S((16,), i32))
    return fn.lower(*args).as_text()


@pytest.mark.parametrize("name", ["solve_fused", "solve_fused_walk",
                                  "solve_dense", "solve_scan"])
def test_jitted_dp_programs_have_stable_names(name):
    """The device trace prints each DP program by this name, and the
    benchmark's kernel readers match on ``solve`` in it."""
    assert f"module @jit_{name} " in _lowered_text(name)


def test_rebuild_span_holds_its_build(tmp_path):
    ex = ManualExecutor()
    rb = SurfaceRebuilder(paper_cost_model("mobilenet_v2", "esp_now"),
                          dict(PROTOCOLS), solver="batched_dp",
                          backend="numpy", executor=ex,
                          pt_scale=(1.0, 4.0), loss_p=(0.0, 0.1))
    nominal = PROTOCOLS["esp_now"].packet_time_s()
    t0 = time.perf_counter()
    assert rb.request(2, {"esp_now": (8 * nominal, 0.2)}) == "queued"
    assert rb.request(2, {"esp_now": (9 * nominal, 0.3)}) == "coalesced"
    assert rb.request(3, {"esp_now": (8 * nominal, 0.2)}) == "queued"
    assert rb.poll(2) is None  # launches one build for both sizes
    time.sleep(0.02)  # the build waits on the executor
    _, spans = traced(tmp_path, ex.run_all)
    (build,) = named(spans, "repro.rebuild")
    assert list(build.stats) == ["queued_ms"]
    # from the first request, not the launch, to the build's start
    assert 20 <= build.stats["queued_ms"] < (time.perf_counter() - t0) * 1e3
    dps = named(spans, "repro.dp")
    assert dps and all(parent(sp, spans) is build for sp in dps)
    assert rb.poll(2) is not None and rb.poll(3) is not None


def test_pipeline_grid_spans_carry_their_counts(tmp_path):
    from repro.configs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.core.planner import pipeline_grid
    from repro.models.graph import experts_touched

    shapes = (ShapeSpec("prefill", "prefill", 2048, 1),
              ShapeSpec("decode", "decode", 4096, 8))
    cfg = get_config("deepseek-v3")
    grid, spans = traced(tmp_path, lambda: pipeline_grid(
        cfg, shapes, (8, 16, 32), range(2, 17)))
    assert {sp.name for sp in spans} <= set(SPANS)
    (outer,) = named(spans, "repro.plan.pipeline")
    assert outer.stats == {"shapes": 2, "mixes": 3, "layers": 63}
    inner = named(spans, "repro.plan.pipeline.profile")
    assert [parent(sp, spans) for sp in inner] == [outer, outer]
    # 61 MLA blocks and the MTP block hold bf16 latent caches and read all
    # of them; no state, no sparse attention, no indices on any cut
    latent = 62 * 576 * 2
    dense = {"sparse_layers": 0, "index_share_layers": 0, "linear_layers": 0,
             "state_bytes": 0, "cut_index_bytes": 0}
    assert [sp.stats for sp in inner] == [
        {"experts_touched": experts_touched(256, 8, 2048), "layers": 63,
         "cache_bytes": latent * 2048, "cache_read_bytes": latent * 2048,
         **dense},
        {"experts_touched": experts_touched(256, 8, 8), "layers": 63,
         "cache_bytes": latent * 8 * 4096,
         "cache_read_bytes": latent * 8 * 4096, **dense}]
    assert grid.size == 2 * 3 * 15 * 2


def test_pipeline_profile_counts_held_state_and_cache(tmp_path):
    """GigaChat3.5: 30 Gated DeltaNet layers hold a float32 state fixed in
    the KV length, 10 MLA layers and 2 MTP blocks a bf16 latent cache."""
    from repro.configs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.core.planner import pipeline_grid

    shapes = (ShapeSpec("prefill", "prefill", 4096, 2),
              ShapeSpec("decode", "decode", 262_144, 8))
    cfg = get_config("gigachat3.5-432b-a28b")
    _, spans = traced(tmp_path, lambda: pipeline_grid(cfg, shapes, (8,), (2, 4)))
    assert {sp.name for sp in spans} <= set(SPANS)
    inner = named(spans, "repro.plan.pipeline.profile")
    state = 64 * 128 * 128 + 3 * (2 * 32 * 128 + 64 * 128)  # a sequence
    got = [{k: sp.stats[k] for k in ("layers", "linear_layers", "state_bytes",
                                     "cache_bytes")} for sp in inner]
    assert got == [
        {"layers": 42, "linear_layers": 30, "state_bytes": 30 * 2 * state * 4,
         "cache_bytes": 12 * 2 * 4096 * 576 * 2},
        {"layers": 42, "linear_layers": 30, "state_bytes": 30 * 8 * state * 4,
         "cache_bytes": 12 * 8 * 262_144 * 576 * 2}]


def test_pipeline_profile_counts_sparse_attention(tmp_path):
    """GLM-5.2: 78 MLA layers and the MTP block attend to the indexer's
    top-2,048; 57 layers share an earlier selection, so 57 cuts ship the
    int32 indices. A decode step past the top-k reads less cache than
    the graph holds; a prefill reads what it holds."""
    from repro.configs import get_config
    from repro.configs.shapes import ShapeSpec
    from repro.core.planner import pipeline_grid

    shapes = (ShapeSpec("prefill", "prefill", 8192, 1),
              ShapeSpec("decode", "decode", 1_048_576, 4))
    cfg = get_config("glm-5.2")
    _, spans = traced(tmp_path, lambda: pipeline_grid(cfg, shapes, (8,), (2, 4)))
    assert {sp.name for sp in spans} <= set(SPANS)
    inner = named(spans, "repro.plan.pipeline.profile")
    keys = ("layers", "sparse_layers", "index_share_layers", "cache_bytes",
            "cache_read_bytes", "cut_index_bytes")
    got = [{k: sp.stats[k] for k in keys} for sp in inner]
    held = 79 * 576 + 22 * 128  # latents and index keys a token, 96,640 B
    read = 57 * 2048 * 576 + 22 * (2048 * 576 + 1_048_576 * 128)  # a sequence
    assert got == [
        {"layers": 80, "sparse_layers": 79, "index_share_layers": 57,
         "cache_bytes": held * 8192 * 2, "cache_read_bytes": held * 8192 * 2,
         "cut_index_bytes": 57 * 8192 * 2048 * 4},
        {"layers": 80, "sparse_layers": 79, "index_share_layers": 57,
         "cache_bytes": held * 4 * 1_048_576 * 2,
         "cache_read_bytes": read * 4 * 2,
         "cut_index_bytes": 57 * 4 * 2048 * 4}]
