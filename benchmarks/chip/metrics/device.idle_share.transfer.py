"""Percent of the traced window in which the device was idle while a
host thread of the runtime relaid or transferred arrays between host
and device (`idle_transfer_s`, trace_phases.py)."""
import trace_phases


def read(rec):
    ph = trace_phases.read(rec)
    if not ph or ph["window_s"] <= 0:
        return None
    return 100.0 * ph["idle_transfer_s"] / ph["window_s"]
