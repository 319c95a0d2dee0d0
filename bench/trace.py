"""Device trace: record a window with JAX's profiler, reduce it to numbers.

``Tracer`` brackets the measured window with ``jax.profiler`` (no Python
tracer, host annotations only) and writes the ``.xplane.pb`` into a
directory the caller owns. ``reduce_xplane`` turns that file into:

* ``busy_ns`` - per device, the union of the intervals in which an
  operation ran (the ``XLA Ops`` and ``Async XLA Ops`` lines of each
  ``/device:TPU:n`` plane);
* ``ops`` - device time per operation (``%name (opcode)``), and
  ``modules`` - device time and executions per XLA module (the ``XLA
  Modules`` line, e.g. ``jit_solve``), so a reader picks a kernel's
  program by the name the trace prints;
* ``gaps`` - the device's idle gaps, each labelled with the benchmark's
  host annotation around it (``bench.*`` spans) and where in that span
  it fell.

Only the process that holds the chip can trace it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


class Tracer:
    """``with Tracer(dir):`` records the enclosed code's device trace."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]


def _union_ns(intervals) -> int:
    total = 0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def short_name(op: str) -> str:
    """``%solve.1 (custom-call)`` for the HLO text the trace prints."""
    head, _, rest = op.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9-]*)\(", rest)
    return f"{head} ({m.group(1)})" if m else head


def load_events(path: str):
    """(device_ops, modules, host_spans): device op events as (plane,
    start, end, name), XLA module executions as (plane, start, end,
    name without its fingerprint), and the benchmark's host spans as
    (start, end, name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops = []
    modules = []
    host_spans = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and "TPU" in name:
            for line in plane.lines:
                if line.name in OPS_LINES:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        device_ops.append((name, s, s + int(ev.duration_ns), ev.name))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((name, s, s + int(ev.duration_ns),
                                        ev.name.split("(")[0]))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        s = int(ev.start_ns)
                        host_spans.append((s, s + int(ev.duration_ns), ev.name))
    return device_ops, modules, host_spans


def reduce_events(device_ops, modules, host_spans) -> dict:
    """The numbers the metric readers take from a trace (see module doc)."""
    by_plane = defaultdict(list)
    ops = defaultdict(int)
    for plane, s, e, name in device_ops:
        by_plane[plane].append((s, e))
        ops[short_name(name)] += e - s
    busy = {p: _union_ns(iv) for p, iv in by_plane.items()}
    mods = defaultdict(lambda: [0, 0])
    for plane, s, e, name in modules:
        mods[name][0] += e - s
        mods[name][1] += 1
    return {"busy_ns": busy, "ops": dict(ops),
            "modules": {k: tuple(v) for k, v in mods.items()},
            "gaps": _gaps(by_plane, host_spans), "n_device_ops": len(device_ops),
            "host_spans": len(host_spans)}


def _gaps(by_plane, host_spans) -> list:
    """Idle gaps of the first device between its busy intervals, inside
    the benchmark's host spans: (ns, label)."""
    if not by_plane:
        return []
    plane = sorted(by_plane)[0]
    merged = []
    for s, e in sorted(by_plane[plane]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    out = []
    spans = sorted(host_spans)
    for lo, hi, name in spans:
        inside = [iv for iv in merged if iv[0] < hi and iv[1] > lo]
        if not inside:
            out.append((hi - lo, f"{name}: no device work"))
            continue
        first, last = inside[0], inside[-1]
        if first[0] > lo:
            out.append((first[0] - lo, f"{name}: before its first device op"))
        for a, b in zip(inside, inside[1:]):
            if b[0] > a[1]:
                out.append((b[0] - a[1], f"{name}: between device ops"))
        if hi > last[1]:
            out.append((hi - last[1], f"{name}: after its last device op"))
    # time between the benchmark's spans, while the device was idle
    for (lo0, hi0, n0), (lo1, hi1, n1) in zip(spans, spans[1:]):
        if lo1 > hi0:
            busy = sum(max(0, min(e, lo1) - max(s, hi0)) for s, e in merged)
            if lo1 - hi0 - busy > 0:
                out.append((lo1 - hi0 - busy, f"between {n0} and {n1}"))
    return out


def reduce_xplane(path: str) -> dict:
    return reduce_events(*load_events(path))


def breakdown(reduced: dict) -> dict:
    """The ``breakdown`` of a ``--trace 1`` result line: the ten device
    operations that took most time and the ten longest idle gaps, by
    what the host was doing (labels merged, seconds summed)."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = defaultdict(int)
    for ns, label in reduced["gaps"]:
        gaps[label] += ns
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[label, ns / 1e9] for label, ns in top]}


def module_ns(reduced: dict, key: str) -> int:
    """Device time of every XLA module whose printed name holds ``key``."""
    return sum(ns for name, (ns, _) in reduced["modules"].items() if key in name)
