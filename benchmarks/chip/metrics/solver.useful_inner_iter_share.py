"""Percent of the inner iterations a vmapped batch runs that its real
rows needed: for each flush of the window (the program's `solve` spans,
one per request, grouped by flush), the sum of its requests' inner
iterations over the rows solved times the largest count among them. A
batch runs to its slowest row, and pad rows repeat row 0."""


def read(rec):
    inner = {a["rid"]: a["inner"] for a in rec["answers"]}
    flushes = {}
    for name, t0, t1, tid, kw in rec["spans"]:
        if name == "solve" and rec["t_start"] <= t0 <= rec["t_end"] \
                and tid in inner:
            key = (t0, t1, kw.get("bucket"))
            flushes.setdefault(key, [kw.get("n_rows", 0), []])[1].append(
                inner[tid])
    useful = executed = 0
    for n_rows, its in flushes.values():
        useful += sum(its)
        executed += max(n_rows, len(its)) * max(its)
    return 100.0 * useful / executed if executed > 0 else None
