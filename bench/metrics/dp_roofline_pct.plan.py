"""The DP kernel's share of its roofline: the least time the chip needs
for the cell's DP problem (``bench/work.py``: the larger of operations
over peak FLOP/s and bytes over peak bytes/s) over the DP program's
device time per call (modules named with ``solve``, as the kernel-time
reader takes them). Bytes bound it at this cell's shapes."""

from bench.trace import module_ns
from bench.work import dp_work, least_time_s

MODULE_KEY = "solve"


def read(run):
    if run.trace is None:
        return None
    ns = module_ns(run.trace, MODULE_KEY)
    if ns == 0:
        return None
    rec = run.records
    per_call_s = ns / 1e9 / len(rec["calls"])
    per_group = rec["grid_size"] // len(rec["ns"])
    fleet = [n for n in rec["ns"] for _ in range(per_group)]
    ops, nbytes = dp_work(fleet, rec["L"])
    least, _ = least_time_s(ops, nbytes, run.device_kind)
    return 100.0 * least / per_call_s
