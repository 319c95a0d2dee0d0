"""The bottleneck DP kernel's share of its roofline in the pipeline
what-ifs: the least time the chip needs for a call's DP problem
(``bench/work.py``'s ``dp_work`` of one shape group's stage counts at
L = 63 with its six bank matrices, times the call's shape groups; the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over
the DP program's device time per call (modules named with ``solve``)."""

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
    ops, nbytes = dp_work(rec["group_fleet"], rec["L"],
                          bank_matrices=rec["bank_matrices"])
    least, _ = least_time_s(rec["groups"] * ops, rec["groups"] * nbytes,
                            run.device_kind)
    return 100.0 * least / per_call_s
