"""Mean megabytes (1e6 bytes) of the stacked A, b, x and action arrays a
flush of the window handed to the executor: the `input_bytes` of the
program's `flush` spans (service/batcher.py, core/batching.py)."""
import numpy as np


def read(rec):
    d = [kw["input_bytes"] for name, t0, _, _, kw in rec["spans"]
         if name == "flush" and "input_bytes" in kw
         and rec["t_start"] <= t0 <= rec["t_end"]]
    return float(np.mean(d)) / 1e6 if d else None
