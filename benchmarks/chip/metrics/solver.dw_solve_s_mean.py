"""Mean seconds of the solve of a window's flush that took a
double-word branch (`dw_gmres` or `dw_lu` on its `flush` span): the
program's per-request `solve` spans, one duration per flush. None where
no flush of the window took one, or the spans carry no such args."""
import numpy as np


def read(rec):
    dw = {kw["flush"] for name, t0, _, _, kw in rec["spans"]
          if name == "flush" and "flush" in kw
          and (kw.get("dw_gmres") or kw.get("dw_lu"))
          and rec["t_start"] <= t0 <= rec["t_end"]}
    d = {kw["flush"]: t1 - t0 for name, t0, t1, _, kw in rec["spans"]
         if name == "solve" and kw.get("flush") in dw}
    return float(np.mean(list(d.values()))) if d else None
