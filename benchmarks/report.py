"""Generate the §Dry-run markdown table into EXPERIMENTS.md (replaces the
<!-- DRYRUN_TABLE --> marker)."""

from __future__ import annotations

import json
from pathlib import Path

from repro.configs import ARCH_IDS, applicable_shapes

ROOT = Path(__file__).resolve().parents[1]
DRYRUN = ROOT / "experiments" / "dryrun"
HBM = 16 * 1024**3


def dryrun_table() -> str:
    lines = [
        "| arch | shape | mesh | compile | mem/dev | fits | HLO flops/dev (per-body) | collectives (weighted wire) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_IDS:
        for shape in applicable_shapes(arch):
            for mesh in ("16x16", "2x16x16"):
                f = DRYRUN / f"{arch}__{shape}__{mesh}.json"
                if not f.exists():
                    lines.append(f"| {arch} | {shape} | {mesh} | MISSING | | | | |")
                    continue
                r = json.loads(f.read_text())
                peak = r["memory"]["peak_estimate_bytes"]
                wire = r.get("collectives_weighted", {}).get("total_wire_bytes", 0)
                lines.append(
                    f"| {arch} | {shape} | {mesh} | {r['compile_s']:.0f}s "
                    f"| {peak / 1e9:.2f} GB | {'Y' if peak < HBM else 'over'} "
                    f"| {r['flops_per_device']:.2e} | {wire / 1e9:.2f} GB |")
    return "\n".join(lines)


def main():
    exp = ROOT / "EXPERIMENTS.md"
    text = exp.read_text()
    text = text.replace("<!-- DRYRUN_TABLE -->", dryrun_table())
    exp.write_text(text)
    print("EXPERIMENTS.md tables updated")


if __name__ == "__main__":
    main()
