"""The program-span reduction (``bench/spans.py``): self time and the
device's idle gaps put down to the innermost ``repro.*`` span, on
events built by hand and on a trace recorded on a TPU v5e."""

from __future__ import annotations

import io
from pathlib import Path

import pytest

from bench import spans as ps
from bench import trace as tr
from bench.tests.conftest import shrink_sweep

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"
A, B = ("/host:CPU", 0), ("/host:CPU", 1)


def _span(s, e, name, thread=A, **stats):
    return (s, e, name, thread, stats)


# repro.sweep [0, 100) holds build [10, 40), dp [40, 70) with a launch
# [45, 60), and rows [70, 95); the benchmark's call span is [0, 120)
LAUNCH = dict(kernel="solve_fused", rows=8, rows_padded=16, lanes=52,
              lanes_padded=128, h2d_bytes=32, d2h_bytes=64)
PROGRAM = [
    _span(0, 100, "repro.sweep", scenarios=8),
    _span(10, 40, "repro.sweep.build"),
    _span(40, 70, "repro.dp"),
    _span(45, 60, "repro.dp.launch", **LAUNCH),
    _span(70, 95, "repro.sweep.rows"),
]
CALL = _span(0, 120, "bench.sweep_call")


def test_self_time_subtracts_direct_children_only():
    red = ps.reduce_spans(PROGRAM)
    assert {k: v["self_ns"] for k, v in red.items()} == {
        "repro.sweep": 100 - 30 - 30 - 25, "repro.sweep.build": 30,
        "repro.dp": 30 - 15, "repro.dp.launch": 15, "repro.sweep.rows": 25}
    assert red["repro.dp"]["ns"] == 30 and red["repro.dp"]["count"] == 1
    # numeric stats sum; the kernel's name is not a count
    assert red["repro.dp.launch"]["stats"] == {
        k: v for k, v in LAUNCH.items() if k != "kernel"}
    twice = ps.reduce_spans(PROGRAM + [_span(200, 260, "repro.sweep.rows")])
    assert twice["repro.sweep.rows"] == {"count": 2, "ns": 85, "self_ns": 85,
                                         "stats": {}, "children": {}}


def test_children_are_counted_by_name():
    red = ps.reduce_spans(PROGRAM + [_span(62, 68, "repro.dp.launch")])
    assert red["repro.sweep"]["children"] == {
        "repro.sweep.build": 1, "repro.dp": 1, "repro.sweep.rows": 1}
    assert red["repro.dp"]["children"] == {"repro.dp.launch": 2}


def test_spans_of_other_threads_are_not_children():
    other = [_span(20, 30, "repro.rebuild", thread=B)]
    red = ps.reduce_spans(PROGRAM + other)
    assert red["repro.sweep.build"]["self_ns"] == 30
    assert red["repro.rebuild"]["self_ns"] == 10


def _gaps(device_ops, host_spans):
    got = {}
    for ns, label in ps.program_gaps(device_ops, host_spans):
        got[label] = got.get(label, 0) + ns
    return got


def test_a_gap_across_spans_splits_by_the_innermost():
    # the device is busy [50, 55): the idle gap [0, 50) crosses the
    # sweep, build, dp and launch spans; [55, 120) the launch, dp, rows,
    # sweep, and then no program span at all
    ops = [(DEV, 50, 55, "%solve_fused.1 = custom-call()")]
    got = _gaps(ops, PROGRAM + [CALL])
    assert got == {
        "bench.sweep_call: repro.sweep": 10 + 5,
        "bench.sweep_call: repro.sweep.build": 30,
        "bench.sweep_call: repro.dp": 5 + 10,
        "bench.sweep_call: repro.dp.launch": 5 + 5,
        "bench.sweep_call: repro.sweep.rows": 25,
        "bench.sweep_call: outside program spans": 20,
    }
    assert sum(got.values()) == 120 - 5


def test_a_gap_outside_every_program_span():
    ops = [(DEV, 0, 10, "%a = fusion()")]
    calls = [_span(20, 50, "bench.sweep_call"),
             # a call on a thread whose program spans are elsewhere
             _span(60, 90, "bench.sweep_call", thread=B)]
    program = [_span(60, 90, "repro.sweep")]  # thread A, not B
    got = _gaps(ops, calls + program)
    assert got == {"bench.sweep_call: outside program spans": 30 + 30}
    assert ps.program_gaps([], calls) == []  # no device: nothing to split


def test_idle_gaps_by_span_ranks_merged_labels():
    ops = [(DEV, 50, 55, "%solve_fused.1 = custom-call()")]
    red = ps.reduce_program_events(ops, PROGRAM + [CALL])
    top = ps.idle_gaps_by_span(red, top=2)
    assert top == [["bench.sweep_call: repro.sweep.build", 30e-9],
                   ["bench.sweep_call: repro.sweep.rows", 25e-9]]


def test_program_metrics_of_one_call():
    ops = [(DEV, 50, 55, "%solve_fused.1 = custom-call()")]
    red = ps.reduce_program_events(ops, PROGRAM + [CALL])
    window_s = 120e-9
    got = ps.program_metrics(red, window_s)
    want = {
        "assemble_pct": 100 * 25 / 120,
        "dp_d2h_mb": 64e-6, "dp_h2d_mb": 32e-6,
        "dp_lane_pad_pct": 100 * (1 - 52 / 128), "dp_row_pad_pct": 50.0,
        "dp_launches": 1.0, "scenarios_per_s": 8 / window_s,
        # of 115 ns idle: build 30, dp 15, launch 10, rows 25
        "idle_in_sweep_children_pct": 100 * 80 / 115,
    }
    assert got == pytest.approx(want)
    # spans a program does not open give no number
    assert set(got).isdisjoint({"enumerate_pct", "dp_fetch_pct",
                                "dp_reconstruct_pct"})
    assert ps.program_metrics(ps.reduce_program_events(ops, [CALL]), 1.0) == {}


def _inside_calls_s(reduced_trace) -> float:
    """Idle seconds inside the benchmark's spans, by ``bench/trace.py``."""
    return sum(ns for ns, label in reduced_trace["gaps"]
               if not label.startswith("between ")) / 1e9


def test_a_trace_without_program_spans():
    """The trace of a program that opens no ``repro.*`` span (three
    ``sweep`` calls, recorded on a TPU v5e before the program had any):
    no spans, and all the idle time inside the calls is outside them."""
    path = str(DATA / "r50_sweep.xplane.pb")
    red = ps.reduce_program_xplane(path)
    assert red["spans"] == {}
    labels = {label for _, label in red["program_gaps"]}
    assert labels == {"bench.sweep_call: outside program spans"}
    idle = sum(ns for ns, _ in red["program_gaps"]) / 1e9
    assert idle == pytest.approx(_inside_calls_s(tr.reduce_xplane(path)))



def test_the_traced_run_reduces_its_program_spans():
    """``python3 -m bench.spans`` on a tiny r50-whatif (CPU, so the trace
    has no device plane and no idle gaps): the cell's result line, then
    the program's spans and numbers from the same trace."""
    from repro.core.spans import SPANS

    out = io.StringIO()
    line = ps.main(["--workload", "r50-whatif", "--seed", str(2**31 + 7),
                    "--seconds", "1"], out=out, require_tpu=False,
                   traffic_overrides=shrink_sweep, err=io.StringIO())
    result, last = out.getvalue().strip().split("\n")[-2:]
    assert '"correct": true' in result and last.startswith('{"program_metrics"')
    assert set(line["spans"]) <= set(SPANS)
    calls = line["spans"]["repro.sweep"]["count"]
    assert calls >= 1 and line["spans"]["repro.sweep.enumerate"]["count"] == calls
    got = line["program_metrics"]
    # four fleet sizes: four device stacks, one fused launch each
    assert got["dp_launches"] == 4
    assert got["dp_lane_pad_pct"] == pytest.approx(100 * (1 - 52 / 128))
    assert {"enumerate_pct", "assemble_pct", "dp_fetch_pct",
            "dp_reconstruct_pct", "dp_d2h_mb", "scenarios_per_s"} <= set(got)
