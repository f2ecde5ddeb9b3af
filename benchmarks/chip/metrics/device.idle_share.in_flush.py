"""Percent of the traced window in which the device was idle while the
driving thread was inside a `flush` span: stacking, dispatch or fetch
of one bucket's batch (trace_phases.py). Nothing where the trace holds
no `flush` span."""
import trace_phases


def read(rec):
    ph = trace_phases.read(rec)
    if not ph or not ph["flushes"] or ph["window_s"] <= 0:
        return None
    idle = sum(v for k, v in ph["idle_by_phase"].items()
               if k in trace_phases.IN_FLUSH)
    return 100.0 * idle / ph["window_s"]
