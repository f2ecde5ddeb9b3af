"""Percent of the traced window in which no operation ran on the
device: 1 - busy / window, busy being the union of the device's
operation intervals, averaged over the chips used (trace_reduce.py)."""


def read(rec):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
