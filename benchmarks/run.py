"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sections:
  table2  — dense randsvd (paper Table 2 + Fig. 2 usage distribution)
  table2fp8 — the dense grid re-run with the fp8-extended action space
            (SOLVER_LADDER_FP8; reduced scale, honestly recorded)
  table6  — penalty ablation (paper Table 6 + Fig. 4); shares solve caches
            with table2 via the env registry
  table4  — sparse SPD (paper Tables 3/4/5)
  tasks   — per-TunableTask training throughput (GMRES-IR vs CG-IR
            through the shared AutotuneEngine)
  sharded — SolveExecutor scaling: solves/s vs data-axis width on a
            forced 8-device host mesh (DESIGN.md §7; subprocess)
  backend — precision-backend comparison: jnp oracle vs pallas kernels,
            solves/s + req/s per task (DESIGN.md §6)
  service — online autotuning service: req/s + latency vs micro-batch size
  cold_start — compile-cliff arms (DESIGN.md §12): cold vs sync-warmed vs
            disk-cache-restart boots, first-hit vs steady-state per bucket
            (subprocess per arm)
  kernels — chop / qmatmul microbenchmarks
  roofline— summary rows from launch/dryrun artifacts, if present

After the selected sections run, a top-level ``BENCH_results.json`` is
written with the headline perf numbers (req/s + p50/p99 from the service
bench, solves/s per task) plus execution metadata (`jax.device_count()`,
mesh shape of the sharded sweep) so the trajectory accumulates across
PRs.

Flags: --full (paper-scale §5.1), --only <name>, --skip-solver.
"""
from __future__ import annotations

import json
import os
import sys

# Script entry (`python benchmarks/run.py`) puts benchmarks/ on sys.path,
# not the repo root the `benchmarks.*` imports need.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax

jax.config.update("jax_enable_x64", True)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_RESULTS_PATH = os.path.join(REPO_ROOT, "BENCH_results.json")

_PRINTED = 0


def _flush(rows):
    global _PRINTED
    for r in rows[_PRINTED:]:
        print(r, flush=True)
    _PRINTED = len(rows)


def write_bench_results(path: str = BENCH_RESULTS_PATH) -> dict:
    """Aggregate headline numbers from the per-section reports into one
    top-level JSON (req/s, p50/p99, solves/s per task).

    Merges into the existing file: a section is only rewritten when its
    per-section report is present in benchmarks/results/, so re-running
    one section never erases the others' committed trajectory."""
    from benchmarks.common import load_report
    summary = {"service": None, "tasks": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                summary.update(json.load(f))
        except (OSError, json.JSONDecodeError):
            pass
    summary["metadata"] = {"jax_device_count": jax.device_count(),
                           "jax_backend": jax.default_backend(),
                           **summary.get("metadata", {})}
    summary["metadata"]["jax_device_count"] = jax.device_count()
    summary["metadata"]["jax_backend"] = jax.default_backend()
    summary["metadata"]["jax_version"] = jax.__version__
    service = load_report("service_bench")
    if service:
        summary["service"] = [
            {"max_batch": s["max_batch"],
             "rps": s["rps"],
             "p50_s": s["latency_s"]["p50"],
             "p99_s": s["latency_s"]["p99"],
             "pad_waste_frac": s.get("pad_waste_frac")}
            for s in service.get("settings", [])]
        if service.get("obs_overhead"):
            # Metrics-on vs metrics-off req/s (acceptance bar: <= 5%).
            summary["service_obs_overhead"] = service["obs_overhead"]
        if service.get("http_front_door"):
            # Same trace over the asyncio front door (DESIGN.md §9.1):
            # wire + JSON + admission overhead vs in-process serving.
            summary["http_front_door"] = service["http_front_door"]
    tasks = load_report("task_bench")
    if tasks:
        summary["tasks"] = {
            t["task"]: {"solves_per_s": t["solves_per_s"],
                        "n_solves": t["n_solves"],
                        "reward_last": t["reward_last"]}
            for t in tasks.get("tasks", [])}
    backend = load_report("precision_backend_bench")
    if backend:
        summary["precision_backend"] = {
            "pallas_mode": backend.get("pallas_mode"),
            "entries": [
                {"task": e["task"], "backend": e["backend"],
                 "mode": e["mode"],
                 "solves_per_s": e["solves_per_s"],
                 "req_per_s": e["req_per_s"]}
                for e in backend.get("entries", [])]}
        if backend.get("lu_trisolve"):
            # Strict row-loop vs blocked LU+trisolve pipeline
            # (DESIGN.md §6.4), with per-n blocked/strict speedups.
            entries = backend["lu_trisolve"]
            strict = {(e["n"], e["backend"]): e["solves_per_s"]
                      for e in entries if e["variant"] == "strict"}
            summary["lu_trisolve"] = [
                dict(e, speedup_vs_strict=(
                    e["solves_per_s"] / strict[(e["n"], e["backend"])]
                    if e["variant"] == "blocked"
                    and strict.get((e["n"], e["backend"])) else None))
                for e in entries]
    sharded = load_report("task_bench_sharded")
    if sharded:
        # Honest labeling: host devices share one CPU — the sweep shows
        # partition/dispatch overhead vs data width, not HW speedup.
        summary["task_bench_sharded"] = {
            "label": sharded["label"], "note": sharded["note"],
            "device_count": sharded["device_count"],
            "n": sharded["n"], "chunk": sharded["chunk"],
            "local_solves_per_s": sharded["local_solves_per_s"],
            "entries": [{"data": e["data"], "mesh_shape": e["mesh_shape"],
                         "solves_per_s": e["solves_per_s"],
                         "speedup_vs_local": e["speedup_vs_local"]}
                        for e in sharded["entries"]]}
        summary["metadata"]["sharded_mesh"] = \
            sharded["entries"][-1]["mesh_shape"]
        summary["metadata"]["sharded_device_count"] = \
            sharded["device_count"]
    cold = load_report("cold_start")
    if cold:
        # DESIGN.md §12: first-hit vs steady-state per arm + the
        # counter-based warm-restart proof; the persistent-cache-hot
        # flag rides the metadata so every headline number carries
        # whether it was produced against a warm compile cache.
        summary["cold_start"] = {
            "note": cold.get("note"),
            "warm_restart_zero_fresh_compiles":
                cold.get("warm_restart_zero_fresh_compiles"),
            "arms": {
                arm: {"boot_to_ready_s": a.get("boot_to_ready_s"),
                      "boot_to_first_solve_s":
                          a.get("boot_to_first_solve_s"),
                      "executor_compiles": a.get("executor_compiles"),
                      "compile_cache": a.get("compile_cache"),
                      "buckets": a.get("buckets")}
                for arm, a in cold.get("arms", {}).items()}}
        summary["metadata"]["compile_cache_hot"] = bool(
            cold.get("warm_restart_zero_fresh_compiles"))
    fp8 = load_report("table2_fp8")
    if fp8:
        w1 = fp8.get("settings", {}).get("W1", {})
        summary["table2_fp8"] = {
            "ladder": fp8.get("ladder"),
            "n_actions": fp8.get("n_actions"),
            "scale": fp8.get("scale"),
            "usage_per_solve": w1.get("usage_per_solve"),
            "usage_per_range": w1.get("usage_per_range"),
            "table": w1.get("table"),
            "fp64_baseline": fp8.get("fp64_baseline", {}).get("table")}
    with open(path, "w") as f:
        json.dump(summary, f, indent=1, default=float)
    return summary


def main() -> None:
    args = set(sys.argv[1:])
    full = "--full" in args
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
    rows = ["name,us_per_call,derived"]
    env_registry = {}

    def want(name, solver=True):
        if solver and "--skip-solver" in args:
            return False
        return only is None or only == name

    _flush(rows)
    # The sections that boot child processes run first, before this
    # process touches a device: a chip belongs to one process at a time,
    # and a child cannot take it from a parent that holds it.
    if want("sharded"):
        from benchmarks import task_bench
        rows += task_bench.run_sharded(full=full)
        _flush(rows)
    if want("cold_start"):
        from benchmarks import cold_start
        rows += cold_start.run(full=full)
        _flush(rows)
    if want("table2"):
        from benchmarks import table2_dense
        rows += table2_dense.run(full=full, env_registry=env_registry)
        _flush(rows)
    if want("table6"):
        from benchmarks import table6_ablation
        rows += table6_ablation.run(full=full, env_registry=env_registry)
        _flush(rows)
    if want("table4"):
        from benchmarks import table4_sparse
        rows += table4_sparse.run(full=full)
        _flush(rows)
    if want("tasks"):
        from benchmarks import task_bench
        rows += task_bench.run(full=full)
        _flush(rows)
    if want("table2fp8"):
        from benchmarks import table2_dense
        rows += table2_dense.run_fp8(full=full)
        _flush(rows)
    if want("backend"):
        from benchmarks import precision_backend_bench
        rows += precision_backend_bench.run(full=full)
        _flush(rows)
    if want("service"):
        from benchmarks import service_bench
        rows += service_bench.run(full=full)
        _flush(rows)
    if want("kernels", solver=False):
        from benchmarks import kernel_bench
        rows += kernel_bench.run(full=full)
        _flush(rows)
    if want("roofline", solver=False):
        from benchmarks import roofline
        rows += roofline.run()
        _flush(rows)
    write_bench_results()


if __name__ == "__main__":
    main()
