"""Percent of the window's flushes that ran GMRES on the double-word
carrier: the `dw_gmres` arg of the program's `flush` spans
(service/batcher.py, tasks/gmres_ir.py). None where the spans carry no
such arg (a program without the double-word carrier)."""


def read(rec):
    d = [bool(kw["dw_gmres"]) for name, t0, _, _, kw in rec["spans"]
         if name == "flush" and "dw_gmres" in kw
         and rec["t_start"] <= t0 <= rec["t_end"]]
    return 100.0 * sum(d) / len(d) if d else None
