"""Percent of the device's busy time spent in the Pallas kernels (chop,
qmv, qmatmul, trisolve), from the trace (trace_reduce.py)."""


def read(rec):
    t = rec.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    k = sum(v["seconds"] for v in t["kernels"].values())
    return 100.0 * k / t["busy_s"] if k > 0 else None
