"""Mean seconds a flush of the window spent placing its batch, coercing
it to the carrier and launching the executable, until the call
returned: the program's `flush.dispatch` spans (core/executor.py)."""
import numpy as np


def read(rec):
    d = [t1 - t0 for name, t0, t1, _, _ in rec["spans"]
         if name == "flush.dispatch"
         and rec["t_start"] <= t0 <= rec["t_end"]]
    return float(np.mean(d)) if d else None
