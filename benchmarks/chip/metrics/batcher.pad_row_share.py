"""Percent of the rows the window's flushes solved that were padding:
the window's increase of repro_service_padded_rows_total over that of
repro_service_solver_rows_total (service/instrument.py)."""


def read(rec):
    c0, c1 = rec["counters"]
    rows = c1["solver_rows"] - c0["solver_rows"]
    if rows <= 0:
        return None
    return 100.0 * (c1["padded_rows"] - c0["padded_rows"]) / rows
