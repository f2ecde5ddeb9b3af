"""Percent of the traced window in which the device was idle while the
driving thread was outside every `flush` span: completing a flush's
requests (`flush.complete`) or between flushes (trace_phases.py).
With `device.idle_share.in_flush` it makes up
`device.idle_share.closed`. Nothing where the trace holds no `flush`
span."""
import trace_phases


def read(rec):
    ph = trace_phases.read(rec)
    if not ph or not ph["flushes"] or ph["window_s"] <= 0:
        return None
    idle = sum(v for k, v in ph["idle_by_phase"].items()
               if k not in trace_phases.IN_FLUSH)
    return 100.0 * idle / ph["window_s"]
