"""The harness without a chip: file layout, the work count, the peaks
table, the trace reduction, and the command's refusal off-TPU."""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import trace as tr
from bench.spec import Benchmark
from bench.work import dp_work, least_time_s, peaks

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).with_name("data")


# -- every cell resolves its files by name --------------------------------


@pytest.mark.parametrize("cell", ["r50-whatif", "mnv2-gateway"])
def test_every_workload_resolves(cell, pending_root):
    """Every cell, those in ``BENCHMARK.json`` and those pending, finds
    its configuration, traffic, driver, limits and metric readers."""
    b = Benchmark(pending_root)
    c = b.cell(cell)
    assert c.driver().Driver
    assert c.limits
    for kind in ("end_to_end", "per_layer"):
        for name, (m, reader) in c.readers(kind).items():
            assert callable(reader.read), name
    names = {m["name"] for m in b.metrics_for(cell, "end_to_end")}
    assert "setup_s" in names and len(names) >= 2
    assert b.metrics_for(cell, "per_layer")


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A throw-away cell: a traffic file, a checks file and a
    BENCHMARK.json entry; nothing else changes."""
    root = tmp_path
    shutil.copytree(ROOT / "bench" / "configs", root / "bench" / "configs")
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "checks").mkdir(parents=True)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "whatif-grid.json").read_text())
    traffic["grid"]["energy_budgets"] = [None, {"energy_percentile": 55, "protocol": "esp_now"}]
    traffic["grid"]["device_overrides"] = {"active_power_w": 0.5}
    (root / "bench" / "traffic" / "throwaway.json").write_text(json.dumps(traffic))
    (root / "bench" / "checks" / "throwaway-cell.json").write_text(
        json.dumps({"limits": {"missing": 0}}))
    doc["workloads"].append({"name": "throwaway-cell", "config": "esp32s3-r50",
                             "traffic": "throwaway", "chips": 1, "why": "test"})
    doc["end_to_end"][0].setdefault("workloads", []).append("throwaway-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    c = Benchmark(root).cell("throwaway-cell")
    assert c.traffic["grid"]["energy_budgets"][1]["energy_percentile"] == 55
    assert "scenarios_per_s" in c.readers("end_to_end")
    from bench.drivers.sweep_loop import Traffic

    t = Traffic(c.config, c.traffic, 7)
    assert t.budgets[0] is None and t.budgets[1] > 0
    assert t.grids[0].size == 4 * 4 * 32 * 64 * 2


def test_benchmark_cells_are_listed():
    cells = set(Benchmark(ROOT).cells)
    assert "r50-whatif" in cells and "mnv2-gateway" not in cells


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        Benchmark(ROOT).cell("no-such-cell")


# -- work and peaks ----------------------------------------------------------


def test_dp_work_hand_counts():
    # L=3: 3 candidate cuts per step; n=2 -> 1 step; n=3 -> 2 steps
    ops, nbytes = dp_work([2], 3)
    assert ops == 1 * 3 * 3 + 1 * 3
    # inputs: 2 banks of 3x3, one TX row of 3, one fleet size; outputs:
    # dp 2x3 and parents 1x3
    assert nbytes == (2 * 9 + 3 + 1 + 2 * 3 + 1 * 3) * 4
    ops, nbytes = dp_work([2, 3], 3)
    assert ops == (1 + 2) * 3 * 3 + 2 * 3
    assert nbytes == (2 * 9 + 2 * 3 + 2 + (2 * 3 + 3) + (3 * 3 + 2 * 3)) * 4


def test_least_time_names_its_bound():
    t, bound = least_time_s(1.0, 819e9, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = least_time_s(197e12, 1.0, "TPU v5 lite")
    assert bound == "ops" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")


# -- trace reduction -----------------------------------------------------------


def test_reduce_events_by_hand():
    dev = "/device:TPU:0"
    ops = [(dev, 0, 10, "%a = f32[8] fusion(f32[8] %x)"),
           (dev, 5, 20, "%k = (f32[8]{0}, s32[8]) custom-call(f32[8] %x)"),
           (dev, 40, 50, "%c = f32[8] copy(f32[8] %x)"),
           (dev, 100, 110, "%k = (f32[8]{0}, s32[8]) custom-call(f32[8] %x)")]
    modules = [(dev, 0, 20, "jit_solve"), (dev, 40, 50, "jit_other"),
               (dev, 100, 110, "jit_solve")]
    spans = [(0, 60, "bench.sweep_call"), (90, 120, "bench.sweep_call")]
    red = tr.reduce_events(ops, modules, spans)
    assert red["busy_ns"] == {dev: 20 + 10 + 10}
    assert red["modules"]["jit_solve"] == (20 + 10, 2)
    assert tr.module_ns(red, "solve") == 30
    gaps = dict((label, 0) for _, label in red["gaps"])
    for ns, label in red["gaps"]:
        gaps[label] += ns
    assert gaps["bench.sweep_call: between device ops"] == 20
    assert gaps["bench.sweep_call: after its last device op"] == 10 + 10
    assert gaps["bench.sweep_call: before its first device op"] == 10
    assert gaps["between bench.sweep_call and bench.sweep_call"] == 30
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["%k (custom-call)", 25e-9]
    assert len(bd["idle_gaps"]) <= 10


def _json_twin(path):
    """The same trace's Chrome-trace JSON, read with plain json: device
    busy union (us), jit_solve module time (us), and bench spans."""
    doc = json.load(gzip.open(path))
    evs = doc["traceEvents"]
    pids = {e["pid"] for e in evs if e.get("ph") == "M" and e["name"] == "process_name"
            and e["args"]["name"] == "/device:TPU:0"}
    tids = {e["tid"]: e["args"]["name"] for e in evs if e.get("ph") == "M"
            and e["name"] == "thread_name" and e["pid"] in pids}
    ops = sorted((e["ts"], e["ts"] + e["dur"]) for e in evs if e.get("ph") == "X"
                 and e["pid"] in pids and tids.get(e["tid"]) in ("XLA Ops", "Async XLA Ops"))
    busy, end = 0.0, None
    for s0, e0 in ops:
        if end is None or s0 > end:
            busy += e0 - s0
            end = e0
        elif e0 > end:
            busy += e0 - end
            end = e0
    solve = sum(e["dur"] for e in evs if e.get("ph") == "X" and e["pid"] in pids
                and tids.get(e["tid"]) == "XLA Modules" and e["name"].startswith("jit_solve"))
    spans = sum(1 for e in evs if e.get("ph") == "X" and e["name"] == "bench.sweep_call")
    return busy, solve, spans, len(ops)


def test_reduce_recorded_chip_trace():
    """A trace recorded on a TPU v5e (three ``sweep`` calls of the
    r50-whatif cell, four fused-DP launches each), reduced from its
    ``.xplane.pb`` and checked against its JSON twin read by hand."""
    red = tr.reduce_xplane(str(DATA / "r50_sweep.xplane.pb"))
    busy_us, solve_us, spans, n_ops = _json_twin(DATA / "r50_sweep.trace.json.gz")
    assert list(red["busy_ns"]) == ["/device:TPU:0"]
    assert red["busy_ns"]["/device:TPU:0"] / 1e3 == pytest.approx(busy_us, rel=1e-3)
    assert tr.module_ns(red, "solve") / 1e3 == pytest.approx(solve_us, rel=1e-3)
    assert red["modules"]["jit_solve"][1] == 3 * 4
    assert red["host_spans"] == spans == 3
    assert red["n_device_ops"] >= n_ops > 0
    top = tr.breakdown(red)["device_ops"][0]
    assert top[0] == "%solve.1 (custom-call)"


# -- the command ----------------------------------------------------------------


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_command_refuses_cpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "r50-whatif",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    # past the chip check (there is none here), the run needs the program
    code = ("import bench.run as R; R.run(['--workload', 'r50-whatif', '--seed', '1', "
            "'--seconds', '1', '--trace', '0'], require_tpu=False)")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
