"""Operations and bytes the split DP's problem needs, and the chip peaks.

The count is of the cell's problem, not of any kernel: the DP at the
unpadded (S, N, L), with the inputs the problem has (the two local-cost
matrices of the device bank, one transmission row and one fleet size per
scenario) and the DP and parent tables as outputs, for each scenario up
to its own fleet size. Lane padding, replica rows and a materialised
``C`` are an implementation's waste and are not counted, so a kernel
that drops them reads a higher share of the same yardstick.

Per scenario with fleet size n, each device step k = 2..n evaluates, for
every end layer b, the b - 1 candidate cuts a < b: one add to form the
segment cost (local + transmission), one add to extend the table, one
comparison for the minimum. Values are 4-byte (float32 costs, int32
parents and fleet sizes).
"""

from __future__ import annotations

import json
from pathlib import Path

WORD = 4
OPS_PER_CANDIDATE = 3
PEAKS_FILE = Path(__file__).with_name("peaks.json")


def dp_work(fleet_sizes, L: int, bank_matrices: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one batched DP over scenarios whose fleet
    sizes are ``fleet_sizes`` (an iterable of ints), ``L`` layers."""
    fleet_sizes = [int(n) for n in fleet_sizes]
    S = len(fleet_sizes)
    candidates = L * (L - 1) // 2
    steps = sum(n - 1 for n in fleet_sizes)
    ops = steps * candidates * OPS_PER_CANDIDATE + S * L  # + device-1 row
    inputs = bank_matrices * L * L + S * L + S
    outputs = sum(n * L + (n - 1) * L for n in fleet_sizes)
    return float(ops), float((inputs + outputs) * WORD)


def peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; a device missing from the
    table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        row = table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table['devices'])}") from None
    return row


def least_time_s(ops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """The larger of ops / peak FLOP/s and bytes / peak bytes/s, and
    which of the two bounds it."""
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "ops")
