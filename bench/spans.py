"""Program spans in a device trace: where the host's time and the
device's idle gaps go, by the program's own ``repro.*`` spans.

The planner opens ``repro.*`` spans (``jax.profiler.TraceAnnotation``;
their list is ``repro.core.spans.SPANS``) on the profiler's clock, with
counts as event stats. ``reduce_program_xplane(path)`` reduces a trace
recorded by ``bench.trace.Tracer`` to:

* ``spans`` - per ``repro.*`` name: ``count``, ``ns`` (summed
  durations), ``self_ns`` (each duration minus the union of its direct
  ``repro.*`` children on the same thread) and ``stats`` (each numeric
  stat summed over the events);
* ``program_gaps`` - the idle intervals of the first device inside the
  benchmark's ``bench.*`` spans, each split by the innermost ``repro.*``
  span open on the thread that opened the ``bench.*`` span: (ns, label),
  labelled ``"<bench span>: <repro span>"`` or ``"<bench span>: outside
  program spans"``.

``idle_gaps_by_span`` merges ``program_gaps`` by label for a result
line's breakdown. A trace of a program that opens no ``repro.*`` span
reduces to empty ``spans`` and one ``outside program spans`` label per
``bench.*`` span.

These are additions to ``bench/trace.py``'s reduction, which reads the
same file; they use its definition of a device op
(:func:`bench.trace.load_events`).
"""

from __future__ import annotations

from collections import defaultdict

from bench import trace as tr

PROGRAM_PREFIX = "repro."
OUTSIDE = "outside program spans"


def load_host_spans(path: str):
    """Every ``repro.*`` and ``bench.*`` host event of the trace as
    (start, end, name, thread, stats); ``thread`` is (plane, line)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith((PROGRAM_PREFIX, tr.HOST_SPAN_PREFIX)):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns), ev.name,
                                (plane.name, t), dict(ev.stats)))
    return out


def _by_thread(spans):
    threads = defaultdict(list)
    for sp in spans:
        threads[sp[3]].append(sp)
    for lst in threads.values():
        lst.sort(key=lambda sp: (sp[0], -sp[1]))
    return threads


def _parents(spans):
    """Index of each span's innermost enclosing span (or None); spans of
    one thread, sorted by (start, -end)."""
    parents, stack = [], []
    for i, (s, e, *_) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        parents.append(stack[-1] if stack else None)
        stack.append(i)
    return parents


def _union_within(intervals, lo, hi) -> int:
    return tr._union_ns([(max(s, lo), min(e, hi)) for s, e in intervals
                         if min(e, hi) > max(s, lo)])


def reduce_spans(program_spans) -> dict:
    """``{name: {"count", "ns", "self_ns", "stats", "children"}}`` of
    ``repro.*`` spans given as (start, end, name, thread, stats);
    ``children`` counts the direct children by name (the launches of
    ``repro.dp`` are its ``repro.dp.launch`` children)."""
    out = {}
    for spans in _by_thread(program_spans).values():
        children = defaultdict(list)
        for i, p in enumerate(_parents(spans)):
            if p is not None:
                children[p].append(i)
        for i, (s, e, name, _, stats) in enumerate(spans):
            r = out.setdefault(name, {"count": 0, "ns": 0, "self_ns": 0,
                                      "stats": defaultdict(int),
                                      "children": defaultdict(int)})
            r["count"] += 1
            r["ns"] += e - s
            r["self_ns"] += (e - s) - _union_within(
                [spans[c][:2] for c in children[i]], s, e)
            for c in children[i]:
                r["children"][spans[c][2]] += 1
            for k, v in stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    r["stats"][k] += v
    for r in out.values():
        r["stats"] = dict(r["stats"])
        r["children"] = dict(r["children"])
    return out


def _innermost_segments(spans):
    """Nested spans of one thread, sorted by (start, -end) -> disjoint
    (start, end, name) segments, each named by the innermost span open
    there; time no span covers has no segment."""
    segs, stack, t = [], [], None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for s, e, name, *_ in spans:
        close_until(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    close_until(float("inf"))
    return segs


def _idle_within(busy, lo, hi):
    """Intervals of [lo, hi) outside the merged, sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_gaps(device_ops, host_spans) -> list:
    """(ns, label) pieces of the first device's idle time inside each
    ``bench.*`` span, by the innermost ``repro.*`` span on its thread."""
    planes = sorted({op[0] for op in device_ops})
    if not planes:
        return []
    busy = []
    for s, e in sorted((op[1], op[2]) for op in device_ops if op[0] == planes[0]):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    program = [sp for sp in host_spans if sp[2].startswith(PROGRAM_PREFIX)]
    segments = {th: _innermost_segments(spans)
                for th, spans in _by_thread(program).items()}
    out = []
    for lo, hi, bench, thread, _ in sorted(
            sp for sp in host_spans if sp[2].startswith(tr.HOST_SPAN_PREFIX)):
        segs = segments.get(thread, [])
        for a, b in _idle_within(busy, lo, hi):
            covered = 0
            for s, e, name in segs:
                part = min(e, b) - max(s, a)
                if part > 0:
                    out.append((part, f"{bench}: {name}"))
                    covered += part
            if b - a > covered:
                out.append((b - a - covered, f"{bench}: {OUTSIDE}"))
    return out


def reduce_program_events(device_ops, host_spans) -> dict:
    program = [sp for sp in host_spans if sp[2].startswith(PROGRAM_PREFIX)]
    return {"spans": reduce_spans(program),
            "program_gaps": program_gaps(device_ops, host_spans)}


def reduce_program_xplane(path: str) -> dict:
    device_ops, _, _ = tr.load_events(path)
    return reduce_program_events(device_ops, load_host_spans(path))


def idle_gaps_by_span(reduced: dict, top: int = 10) -> list:
    """The ``top`` labels of ``program_gaps`` by idle time: [[label, s]]."""
    gaps = defaultdict(int)
    for ns, label in reduced["program_gaps"]:
        gaps[label] += ns
    ranked = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return [[label, ns / 1e9] for label, ns in ranked]


def _self_pct(spans, name, window_s):
    r = spans.get(name)
    return None if r is None else 100.0 * r["self_ns"] / 1e9 / window_s


def _pad_pct(stats, used, padded):
    if stats.get(padded):
        return 100.0 * (1.0 - stats[used] / stats[padded])
    return None


def program_metrics(reduced: dict, window_s: float) -> dict:
    """The per-layer numbers the program spans give over a window of
    ``window_s`` seconds (a name is left out when its span is absent):

    * ``enumerate_pct``, ``assemble_pct``, ``dp_fetch_pct``,
      ``dp_reconstruct_pct`` - self time of ``repro.sweep.enumerate``,
      ``repro.sweep.rows``, ``repro.dp.fetch``, ``repro.dp.reconstruct``
      as a share of the window;
    * ``dp_d2h_mb``, ``dp_h2d_mb`` - the summed ``d2h_bytes`` /
      ``h2d_bytes`` of ``repro.dp.launch`` per ``sweep`` call, in MB;
    * ``dp_lane_pad_pct``, ``dp_row_pad_pct`` - the share of the lanes
      and of the rows the launches carry that are padding;
    * ``dp_launches`` - ``repro.dp.launch`` spans per ``repro.dp``;
    * ``scenarios_per_s`` - the summed ``scenarios`` of ``repro.sweep``
      over the window (the traced run's rate);
    * ``idle_in_sweep_children_pct`` - of the device's idle time inside
      the benchmark's spans, the share on a span below ``repro.sweep``
      (not ``repro.sweep`` itself, nor outside every program span)."""
    spans = reduced["spans"]
    if not spans:  # a program without spans
        return {}
    out = {
        "enumerate_pct": _self_pct(spans, "repro.sweep.enumerate", window_s),
        "assemble_pct": _self_pct(spans, "repro.sweep.rows", window_s),
        "dp_fetch_pct": _self_pct(spans, "repro.dp.fetch", window_s),
        "dp_reconstruct_pct": _self_pct(spans, "repro.dp.reconstruct",
                                        window_s),
    }
    calls = spans.get("repro.sweep", {}).get("count", 0)
    launch = spans.get("repro.dp.launch", {}).get("stats", {})
    if calls and launch:
        out["dp_d2h_mb"] = launch["d2h_bytes"] / calls / 1e6
        out["dp_h2d_mb"] = launch["h2d_bytes"] / calls / 1e6
    out["dp_lane_pad_pct"] = _pad_pct(launch, "lanes", "lanes_padded")
    out["dp_row_pad_pct"] = _pad_pct(launch, "rows", "rows_padded")
    dp = spans.get("repro.dp")
    if dp:
        out["dp_launches"] = dp["children"].get("repro.dp.launch", 0) / dp["count"]
    if calls:
        out["scenarios_per_s"] = (spans["repro.sweep"]["stats"]["scenarios"]
                                  / window_s)
    total = sum(ns for ns, _ in reduced["program_gaps"])
    below = sum(ns for ns, label in reduced["program_gaps"]
                if label.split(": ", 1)[1].startswith(("repro.sweep.", "repro.dp")))
    if total:
        out["idle_in_sweep_children_pct"] = 100.0 * below / total
    return {k: v for k, v in out.items() if v is not None}


def main(argv=None, out=None, **run_kw) -> dict:
    """``python3 -m bench.spans --workload <cell> --seed <n> --seconds <s>
    [--keep <dir>]``, from the root of a checkout on the chip: the cell's
    ``--trace 1`` run of ``bench/run.py``, whose result line it prints,
    then one more JSON line with the program-span reduction of the same
    trace (``program_metrics``, ``idle_gaps_by_span`` and, per span, its
    count, seconds and self seconds). ``--keep`` copies the trace there.

    ``bench/run.py`` hands its metric readers ``bench.trace``'s
    reduction only, so this runs it with ``reduce_program_xplane``
    applied to the same file before that file is deleted. ``run_kw``
    goes to :func:`bench.run.run` (a CPU test passes ``require_tpu`` and
    ``traffic_overrides``)."""
    import argparse
    import json
    import shutil
    import sys

    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=main.__doc__.split("\n\n")[0])
    ap.add_argument("--keep", help="directory to copy the .xplane.pb into")
    args, rest = ap.parse_known_args(argv)
    program = {}
    reduce = tr.reduce_xplane

    def reduce_both(path):
        program.update(reduce_program_xplane(path))
        if args.keep:
            shutil.copy(path, args.keep)
        return reduce(path)

    tr.reduce_xplane = reduce_both
    try:
        result = bench_run.run(rest + ["--trace", "1"],
                               out=out or sys.stdout, **run_kw)
    finally:
        tr.reduce_xplane = reduce
    line = {
        "program_metrics": program_metrics(program,
                                           result["device"]["window_s"]),
        "idle_gaps_by_span": idle_gaps_by_span(program),
        "spans": {name: {"count": r["count"], "s": r["ns"] / 1e9,
                         "self_s": r["self_ns"] / 1e9}
                  for name, r in sorted(program["spans"].items())},
    }
    print(json.dumps(line), file=out or sys.stdout, flush=True)
    return line


if __name__ == "__main__":
    main()
