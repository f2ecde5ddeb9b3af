"""Mean seconds the server spent completing a flush of the window: reward,
breaker, Q-update, telemetry and instruments over its requests, the
program's `flush.complete` spans (service/server.py)."""
import numpy as np


def read(rec):
    d = [t1 - t0 for name, t0, t1, _, _ in rec["spans"]
         if name == "flush.complete"
         and rec["t_start"] <= t0 <= rec["t_end"]]
    return float(np.mean(d)) if d else None
