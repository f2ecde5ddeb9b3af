"""Percent of its roofline that the `chop` kernel reached in the traced
window: the least time the chip could take for the operations and bytes
of every call (flops.py, peaks.json), the larger of operations over peak
FLOP/s and bytes over peak bandwidth, over the device time of those
calls (trace_reduce.py). Nothing when the kernel did not run."""


def read(rec):
    t = rec.get("trace")
    k = t and t["kernels"].get("chop")
    if not k or k["seconds"] <= 0 or k["bound_s"] <= 0:
        return None
    return 100.0 * k["bound_s"] / k["seconds"]
