#!/usr/bin/env python3
"""One sweep of an open-loop traffic mix's rate on the chip, to find the
knee: the highest rate the served path sustains without a growing
backlog.

    python3 benchmarks/chip/sweep.py --config dense_gmres_ir \
        --traffic http.open --rates 2,3,4,6,8 --seconds 20 --seed 5 \
        [--write]

The mix need not be a cell of BENCHMARK.json yet: the sweep sets the
rate a cell is then added at. Each rate is one window of the
configuration under the mix (in one process, so the programs load
once). A rate is sustained when the requests due in the window's
second half wait no longer, at the median, than 1.5 times those due in
its first half, and every request is answered. With --write, 0.8 of
the highest sustained rate goes into the cell's traffic file as
"rate", with the knee beside it. One JSON line per rate on standard
output."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts the program's sources on the path)

import numpy as np  # noqa: E402

import bench  # noqa: E402


def sustained(rec: dict) -> dict:
    mid = (rec["t_start"] + rec["t_end"]) / 2
    lat = {h: [a["t_done"] - a["t_submit"] for a in rec["answers"]
               if (a["t_submit"] < mid) == (h == 0)] for h in (0, 1)}
    first = float(np.median(lat[0])) if lat[0] else float("inf")
    second = float(np.median(lat[1])) if lat[1] else float("inf")
    ok = (rec["unanswered"] == 0 and second <= 1.5 * first
          and all(a["code"] == 200 for a in rec["answers"]))
    return {"first_half_p50_s": first, "second_half_p50_s": second,
            "sustained": ok}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    cell = bench.with_files({"name": f"{args.config}.{args.traffic}",
                             "config": args.config,
                             "traffic": args.traffic, "chips": 1})
    run.chip(int(cell["chips"]))
    import jax
    jax.config.update("jax_enable_x64", True)
    entry = bench.module("entries", cell["traffic_file"]["entry"])
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        rec = entry.run(cell, args.seed, args.seconds, rate=rate)
        rec.pop("server")
        e2e = run.end_to_end(rec, 0.0)
        late = [a["t_sent"] - a["t_submit"] for a in rec["answers"]]
        row = {"rate": rate, "due": len(rec["answers"]) + rec["unanswered"],
               "unanswered": rec["unanswered"],
               "latency_p50_s": e2e["latency_p50_s"],
               "latency_p95_s": e2e["latency_p95_s"],
               "send_late_p50_s": float(np.median(late)) if late else None,
               **sustained(rec)}
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = rate
    if args.write and knee is not None:
        path = os.path.join(HERE, "traffic",
                            cell["traffic"] + ".json")
        tr = bench.load_json(path)
        tr["knee"] = knee
        tr["rate"] = round(0.8 * knee, 3)
        with open(path, "w") as f:
            json.dump(tr, f, indent=2)
            f.write("\n")
        print(json.dumps({"knee": knee, "rate": tr["rate"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
