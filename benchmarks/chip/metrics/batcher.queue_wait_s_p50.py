"""Median seconds a request of the window waited in the micro-batcher
between submit and the start of its batch's solve: the program's own
`queue_wait` spans (service/instrument.py)."""
import numpy as np


def read(rec):
    d = [t1 - t0 for name, t0, t1, _, _ in rec["spans"]
         if name == "queue_wait" and rec["t_start"] <= t0 <= rec["t_end"]]
    return float(np.median(d)) if d else None
