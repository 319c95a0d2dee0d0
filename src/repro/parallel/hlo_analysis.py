"""Optimized-HLO analysis: loop-aware collective-byte accounting.

``compiled.cost_analysis()`` and naive text scans count each while-loop
body ONCE, but a layer scan executes its body n_layers times (and the
microbatch scan multiplies again). This module parses the optimized HLO
into computations, extracts while-loop trip counts from their condition
computations (scan counters compare an induction variable against a
constant), and propagates multipliers through the call graph so every
collective is weighted by how many times it actually executes.

The same weighted walk also yields loop-aware totals for any op
predicate.
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 0.125, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%([\w\.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\([^)]*\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_CALL_RE = re.compile(
    r"(?:fusion|call|conditional)\([^)]*\)[^\n]*?(?:calls=|to_apply=)%?([\w\.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_COLL_LINE_RE = re.compile(
    r"=\s+(\(?[\w\[\],{}\s]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += int(n * _DTYPE_BYTES[dt])
    return total


def split_computations(hlo: str) -> tuple[dict[str, str], str | None]:
    """(computation name -> body text, entry computation name)."""
    comps: dict[str, str] = {}
    entry: str | None = None
    name, buf, depth = None, [], 0
    for ln in hlo.splitlines():
        if name is None:
            s = ln.strip()
            m = _COMP_HDR_RE.match(s)
            if m and s.endswith("{"):
                name = m.group(2)
                if m.group(1):
                    entry = name
                buf = [ln]
                depth = ln.count("{") - ln.count("}")
                if depth <= 0:
                    comps[name] = "\n".join(buf)
                    name = None
        else:
            buf.append(ln)
            depth += ln.count("{") - ln.count("}")
            if depth <= 0:
                comps[name] = "\n".join(buf)
                name = None
    return comps, entry


def trip_count(cond_body: str) -> int:
    """Heuristic scan trip count: the largest s32 constant in the loop
    condition (scan counters run 0..N with `compare(i, N), direction=LT`)."""
    consts = [int(c) for c in _CONST_RE.findall(cond_body)]
    return max(consts) if consts else 1


def computation_multipliers(hlo: str) -> dict[str, float]:
    """Execution-count multiplier for every computation, walking from the
    entry through call/fusion (x1) and while (x trip count) edges."""
    comps, entry = split_computations(hlo)
    if entry is None:  # fall back: treat everything as executed once
        return {k: 1.0 for k in comps}

    edges: dict[str, list[tuple[str, float]]] = defaultdict(list)
    for name, body in comps.items():
        for cond, wbody in _WHILE_RE.findall(body):
            n = trip_count(comps.get(cond, ""))
            edges[name].append((wbody, float(n)))
            edges[name].append((cond, float(n)))
        for callee in _CALL_RE.findall(body):
            edges[name].append((callee, 1.0))

    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    work = [entry]
    seen_edges = set()
    while work:
        cur = work.pop()
        for callee, k in edges.get(cur, ()):
            key = (cur, callee, k)
            if key in seen_edges:
                continue
            seen_edges.add(key)
            mult[callee] += mult[cur] * k
            work.append(callee)
    return dict(mult)


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _wire_factor(kind: str, group: int) -> float:
    """Per-device wire bytes per output byte, ring algorithms.

    all-reduce: reduce-scatter + all-gather = 2(s-1)/s x size;
    all-gather: (s-1)/s x gathered size; reduce-scatter: (s-1) x scattered
    output (= (s-1)/s x input); all-to-all: (s-1)/s; permute: 1."""
    s = max(2, group)
    return {
        "all-reduce": 2 * (s - 1) / s,
        "all-gather": (s - 1) / s,
        "reduce-scatter": float(s - 1),
        "all-to-all": (s - 1) / s,
        "collective-permute": 1.0,
    }[kind]


def weighted_collective_bytes(hlo: str) -> dict:
    """Loop-aware collective accounting: each collective's output bytes
    are multiplied by its computation's execution count. Also estimates
    per-device WIRE bytes using ring-collective factors and the replica
    group size parsed per op — the §Roofline collective-term numerator."""
    comps, _entry = split_computations(hlo)
    mult = computation_multipliers(hlo)
    out: dict[str, float] = {}
    counts: dict[str, float] = {}
    wire: dict[str, float] = {}
    for name, body in comps.items():
        m = mult.get(name, 0.0 if len(mult) > 1 else 1.0)
        if m == 0.0:
            continue
        for line in body.splitlines():
            if "-done(" in line or "-done." in line:
                continue
            lm = _COLL_LINE_RE.search(line)
            if not lm:
                continue
            b = shape_bytes(lm.group(1))
            kind = lm.group(2)
            gm = _GROUPS_RE.search(line)
            group = int(gm.group(2)) if gm else 16
            out[kind] = out.get(kind, 0.0) + b * m
            wire[kind] = wire.get(kind, 0.0) + b * m * _wire_factor(kind, group)
            counts[kind] = counts.get(kind, 0.0) + m
    return {"bytes": {k: int(v) for k, v in out.items()},
            "counts": {k: int(v) for k, v in counts.items()},
            "wire_bytes": {k: int(v) for k, v in wire.items()},
            "total_bytes": int(sum(out.values())),
            "total_wire_bytes": int(sum(wire.values()))}
