#!/usr/bin/env python3
"""The two readings each compared number's limit is set from, for one
cell, in one process on the chip:

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 11,12,13 --seconds 10

For each seed: a run of the cell (set-up, window, drain, as run.py makes
it), its answers compared with the reference (the lower reading: the
largest a sound run gives), and the control on the same sampled
systems and actions: the reference computed on the next narrower
carrier in the program's place (the upper reading: the smallest the
control gives). One process serves all seeds, so the programs compile
and load once. Writes one JSON line per seed to standard output, with
every sampled answer beside the reference and the control."""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts the program's sources on the path)

import bench  # noqa: E402
import correctness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = bench.workload(args.workload)
    run.chip(int(cell["chips"]))
    import jax
    jax.config.update("jax_enable_x64", True)
    entry = bench.module("entries", cell["traffic_file"]["entry"])
    cfg = cell["config_file"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rec = entry.run(cell, seed, args.seconds)
        rec.pop("server")
        t1 = time.perf_counter()
        pool, answers = rec["pool"], rec["answers"]
        numbers, detail = correctness.compare(cfg, pool, answers, seed)
        t2 = time.perf_counter()
        kap = correctness.Kappas(pool)
        first = {}
        for a in answers:
            first.setdefault(correctness.key(a), a)
        picked = [first[k] for k in correctness.sample(
            cfg, kap, answers, int(cfg["correct"]["sample"]), seed)]
        ctrl = correctness.control(cfg, pool, picked)
        ctrl_rows = correctness.rows(cfg, pool, kap, ctrl)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "answers": len(answers), "sampled": len(detail),
            "program": {k: v["value"] for k, v in numbers.items()},
            "control": {
                "answer_gap": max((r["gap"] for r in ctrl_rows),
                                  default=0.0),
                "bound_excess": correctness.excess(cfg, pool, kap, ctrl)},
            "end_to_end": run.end_to_end(rec, 0.0),
            "statuses": [a["status"] for a in answers],
            "window_compiles": rec["window_compiles"],
            "run_s": t1 - t0, "reference_s": t2 - t1,
            "control_s": time.perf_counter() - t2,
            "rows": detail, "control_rows": ctrl_rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
