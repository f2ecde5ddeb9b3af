#!/usr/bin/env python3
"""Chip benchmark of the precision-autotuning solve service.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the accelerator it is started on:
loads the cell's configuration and traffic files by name, builds the
server through the program's public constructors, warms every program
the window runs, measures for `--seconds`, then checks the window's
answers against the plain reference (correctness.py). With `--trace 0`
the result carries the cell's end-to-end metrics; with `--trace 1` a
profiler trace of a few seconds of the window and the per-layer metrics
(metrics/<name>.py), the device's busy and window seconds, and the
breakdown of device time and idle gaps.

It refuses to run, and prints no result, without a TPU, with a device
kind that peaks.json does not list, or with fewer chips than the cell
asks for. The last line of standard output is the result's JSON object.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(REPO, "src"))

import bench  # noqa: E402
import correctness  # noqa: E402

TRACE_DIR = os.path.join(REPO, ".bench", "trace")
CACHE_DIR = os.path.join(REPO, ".cache", "xla")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class NoChip(SystemExit):
    pass


def chip(chips: int, devices=None, peaks=None):
    """(devices, peak table row) of the accelerator this process holds;
    raises NoChip without a TPU, with an unknown device kind, or with
    fewer than `chips` devices."""
    if devices is None:
        # The compile cache stays inside the checkout, at a fixed path,
        # whatever the environment names: JAX reads its own variable when
        # imported, and the program takes the directory JAX has.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        import jax
        devices = jax.devices()
    if peaks is None:
        peaks = bench.load_json(os.path.join(HERE, "peaks.json"))
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"run.py: needs a TPU; JAX found {dev.platform!r} "
                     f"({dev.device_kind})")
    if dev.device_kind not in peaks:
        raise NoChip(f"run.py: device kind {dev.device_kind!r} is not in "
                     f"peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        raise NoChip(f"run.py: the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices, peaks[dev.device_kind]


def end_to_end(rec: dict, setup_s: float) -> dict:
    """The user-facing numbers over every request the window sent."""
    ans = rec["answers"]
    attempted = len(ans) + rec["unanswered"]
    lat = bench.latencies(rec)
    conv = [a for a in ans if a["status"] == 0]
    in_window = [a for a in conv if a["t_done"] <= rec["t_end"]]
    return {
        "accurate_solves_per_s": len(in_window) / rec["seconds"],
        "latency_p50_s": bench.percentile(lat, 50),
        "latency_p95_s": bench.percentile(lat, 95),
        "converged_share": 100.0 * len(conv) / max(attempted, 1),
        "setup_s": setup_s,
    }


def failed_count(rec: dict) -> int:
    return rec["unanswered"] + sum(
        1 for a in rec["answers"] if a["status"] == 3 or a["expired"])


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (KeyError, TypeError, AttributeError):
            pass
    return max(peaks) if peaks else 0


def run(args, devices, peak, entry_run=None) -> dict:
    cell = bench.workload(args.workload)
    entry = bench.module("entries", cell["traffic_file"]["entry"])
    trace_dir = (os.path.join(TRACE_DIR, args.workload)
                 if args.trace else None)
    if trace_dir:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = (entry_run or entry.run)(cell, args.seed, args.seconds,
                                   trace_dir=trace_dir)
    setup_s = rec["t_start"] - T0
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes":
              memory_peak(devices)}
    rec.pop("server", None)           # free the program's state
    out = {"cell": cell, "rec": rec, "device": device}
    if args.trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, peak)
        rec["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in cell["per_layer"]:
            v = bench.module("metrics", m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": red["device_ops"][:10],
                            "idle_gaps": red["idle_gaps"][:10]}
    else:
        e2e = end_to_end(rec, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    out["metrics"] = metrics
    out["setup_s"] = setup_s
    t_ref = time.perf_counter()
    numbers, detail = correctness.compare(
        cell["config_file"], rec["pool"], rec["answers"], args.seed)
    out["checks"] = numbers
    out["worst"] = max(detail, key=lambda r: r["gap"], default=None)
    out["sampled"] = len(detail)
    out["reference_s"] = time.perf_counter() - t_ref
    return out


def decide(out):
    """(correct, numbers): every compared number within its limit, no
    compile inside the window, and every request of the window
    answered."""
    rec = out["rec"]
    numbers = dict(out["checks"])
    numbers["window_compiles"] = {"value": sum(rec["window_compiles"]),
                                  "limit": 0}
    numbers["unanswered"] = {"value": rec["unanswered"], "limit": 0}
    return correctness.verdict(numbers), numbers


def report(args, out) -> int:
    rec = out["rec"]
    ans = rec["answers"]
    exe, backend = rec["window_compiles"]
    ok, numbers = decide(out)
    statuses = {}
    arms = {}
    for a in ans:
        statuses[a["status"]] = statuses.get(a["status"], 0) + 1
        arms[a["action"]] = arms.get(a["action"], 0) + 1
    flushes = {}
    for name, t0, t1, tid, kw in rec["spans"]:
        if name == "solve" and rec["t_start"] <= t0 <= rec["t_end"]:
            flushes.setdefault(kw.get("bucket"), set()).add((t0, t1))
    print(f"setup_s={out['setup_s']:.3f} warmup_s={rec['warmup_s']:.3f} "
          f"warmup_errors={rec['warmup_errors']} cache={rec['cache']}",
          flush=True)
    print(f"window: {len(ans)} answers ({rec['unanswered']} unanswered), "
          f"statuses {dict(sorted(statuses.items()))}, "
          f"flushes per bucket "
          f"{ {b: len(v) for b, v in sorted(flushes.items())} }, "
          f"arms used {len(arms)}, compiles in window executor={exe} "
          f"backend={backend}", flush=True)
    print(f"reference: {out['sampled']} answers compared in "
          f"{out['reference_s']:.3f} s; farthest from it: {out['worst']}",
          flush=True)
    result = {"correct": bool(ok), "attempted": len(ans) + rec["unanswered"],
              "failed": failed_count(rec), "metrics": out["metrics"],
              "device": out["device"]}
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    result["checks"] = numbers
    for k, v in numbers.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    cell = bench.workload(args.workload)
    devices, peak = chip(int(cell["chips"]))
    import jax
    jax.config.update("jax_enable_x64", True)     # as the service runs
    return report(args, run(args, devices, peak))


if __name__ == "__main__":
    sys.exit(main())
