"""Mean seconds a flush of the window spent stacking its padded rows
into the fixed-shape batch: the program's `flush.stack` spans
(core/batching.py)."""
import numpy as np


def read(rec):
    d = [t1 - t0 for name, t0, t1, _, _ in rec["spans"]
         if name == "flush.stack" and rec["t_start"] <= t0 <= rec["t_end"]]
    return float(np.mean(d)) if d else None
