#!/usr/bin/env python3
"""Smoke test of the served solve path on a TPU.

Drives the system the way a user does — `AutotuneServer` with sync
warmup, the platform's default precision backend and executor, and the
`serve_http` front door — on a seeded mix of the paper's Table 2 dense
randsvd systems, then checks the answers.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # ShardedExecutor(data=4) vs LocalExecutor

One chip: GMRES-IR over ``POST /v1/solve:sync`` at buckets 128, 256 and
512 (8 requests each, ``max_batch=8``, the reduced action space), a few
CG-IR solves on seeded SPD systems through the same server stack, and a
re-solve of a few rows on the host CPU with the jnp backend on the same
f32 carrier. ``--chips 4`` serves the same GMRES-IR requests through
`ShardedExecutor(data=4)` and compares every row with `LocalExecutor` on
one of those chips, in the same process.

Checks: every request answers 200/"done"; no flush-loop restart and no
warmup error; the backend is the compiled Pallas one; the compiled
executables hold the expected Pallas kernels (`chop` and `qmv` at every
bucket, `qmatmul` and `trisolve` from 256 up); every converged request
meets the error bounds stated in `error_bounds`; the cross-checks agree
within the tolerance stated in `agree`. Timings printed here are smoke
timings, not metrics. The last line of standard output is
``{"ok": true, "device": {...}}``; any failed check exits non-zero
without it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402
from collections import Counter  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import jax  # noqa: E402


def require_tpu() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
                 f"({dev.device_kind})")


require_tpu()
jax.config.update("jax_enable_x64", True)     # as the service runs
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.core import (CONVERGED, FAILED, Discretizer,  # noqa: E402
                        LocalExecutor, PrecisionPolicy, QTable,
                        ShardedExecutor, aot, executor_compile_count,
                        reduced_action_space)
from repro.core.executor import batch_callable  # noqa: E402
from repro.data.matrices import randsvd_dense, sparse_spd  # noqa: E402
from repro.kernels.qmatmul.ops import QGEMM_MAX_KP  # noqa: E402
from repro.kernels.qmatmul.ref import LANE  # noqa: E402
from repro.kernels.trisolve.trisolve import MAX_N  # noqa: E402
from repro.precision import FORMATS, JnpBackend, PallasBackend  # noqa: E402
from repro.service import (AutotuneServer, BatcherConfig,  # noqa: E402
                           OnlineConfig)
from repro.service.http import HttpConfig, serve_http  # noqa: E402
from repro.solvers.ir import IRConfig, gmres_ir_batch  # noqa: E402
from repro.tasks import CGIRTask, GMRESIRTask  # noqa: E402

SEED = 20260
BUCKETS = (128, 256, 512)
# Request sizes per bucket: the paper's n in 100-500, drawn inside each
# bucket's range so that every request lands in 128, 256 or 512.
N_RANGE = {128: (100, 128), 256: (129, 256), 512: (385, 500)}
PER_BUCKET = 8
MAX_BATCH = 8
CG_SIZES = (110, 120, 200, 240)
U32 = 2.0 ** -24          # unit roundoff of the f32 carrier
# Kernels each served bucket must hold: the strict path below the
# blocking threshold (256) rounds with `chop` and multiplies with `qmv`;
# from 256 up the blocked LU adds the `qmatmul` trailing update and the
# blocked substitution adds `trisolve`.
STRICT_KERNELS = {"chop", "qmv"}
BLOCKED_KERNELS = STRICT_KERNELS | {"qmatmul", "trisolve"}

FAILURES = []


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"check {name}: {'ok' if ok else 'FAIL'}"
          + (f" ({detail})" if detail else ""), flush=True)
    if not ok:
        FAILURES.append(name)


def error_bounds(names, n: int, kappa_inf: float):
    """(ferr, nbe) bounds for a converged request, by its precisions.

    The carrier is f32, so no step is more accurate than f32: the update
    precision u_w = unit roundoff of u (x is stored in u) and the
    residual precision u_r = unit roundoff of u_r, both floored at 2^-24.
    Iterative refinement's limiting backward error is the rounding of
    the stored update plus the accumulated rounding of the residual, at
    most n * u_r for a length-n dot; the forward error multiplies the
    residual term by kappa_inf(A). The factor 10 covers the f32
    evaluation of the metrics themselves. Where kappa_inf * u_r reaches
    1 the forward-error bound no longer binds, honestly so: the f32
    carrier cannot resolve such a system."""
    u_w = max(FORMATS[names[1]].unit_roundoff, U32)
    u_r = max(FORMATS[names[3]].unit_roundoff, U32)
    return 10 * (u_w + n * kappa_inf * u_r), 10 * (u_w + n * u_r)


def agree(a: dict, b: dict, bounds) -> bool:
    """Two solves of one system under one action agree when their ferr
    and nbe differ by no more than the error bounds, and both failed or
    neither did. Both round every stored value with the same integer
    algorithm; they differ in carrier arithmetic the bounds allow for:
    the order of f32 reductions inside dots, and the platforms' own f32
    division, square root and subnormal handling. The stopping test
    compares ||z|| with thresholds, so a row that sits at one may stop
    as converged on one platform and as stagnated on the other, at the
    same accuracy: the non-failure statuses are not compared."""
    ferr_b, nbe_b = bounds
    return ((int(a["status"]) == FAILED) == (int(b["status"]) == FAILED)
            and abs(a["ferr"] - b["ferr"]) <= ferr_b
            and abs(a["nbe"] - b["nbe"]) <= nbe_b)


def kernel_counts(task, bucket: int) -> Counter:
    """Pallas custom calls, by kernel name, in the compiled executables
    the task's executor serves `bucket` with."""
    disp = batch_callable(task.executor, None, task.lowerable_for(bucket))
    counts = Counter()
    for shapes, exe in disp.executables.items():
        if shapes[0][0][-1] != bucket:      # (A's shape, dtype) first
            continue
        for line in exe.as_text().splitlines():
            if 'custom_call_target="tpu_custom_call"' in line:
                m = re.search(r"/(\w+)/pallas_call", line)
                counts[m.group(1) if m else "?"] += 1
    return counts


def make_requests(rng):
    reqs = []
    for bucket in BUCKETS:
        lo, hi = N_RANGE[bucket]
        for _ in range(PER_BUCKET):
            n = int(rng.integers(lo, hi + 1))
            kappa = 10.0 ** rng.uniform(1.0, 9.0)
            s = randsvd_dense(n, kappa, rng)
            reqs.append({"bucket": bucket, "system": s,
                         "kappa_inf": float(np.linalg.cond(s.A, np.inf))})
    return reqs


def exploring_policy(task, systems, seed: int) -> PrecisionPolicy:
    """An untrained policy over the reduced action space. Its greedy arm
    is the all-fp64 one; the server explores at eps = 0.5 (below), so
    the rows of one batch carry different precision actions — the
    per-row format ids the kernels are vmapped over."""
    space = reduced_action_space()
    feats = np.stack([task.feature_of(s) for s in systems])
    disc = Discretizer.fit(feats, [3] * feats.shape[1])
    return PrecisionPolicy(space, disc,
                           QTable(disc.n_states, space.n_actions,
                                  seed=seed))


def build_server(task, systems, buckets, seed):
    return AutotuneServer(
        exploring_policy(task, systems, seed), task,
        batcher_cfg=BatcherConfig(max_batch=MAX_BATCH, max_wait_s=2.0,
                                  bucket_step=128, min_bucket=128),
        online_cfg=OnlineConfig(eps0=0.5, eps_min=0.5),
        seed=seed, warmup="sync", warmup_buckets=list(buckets))


def post(url: str, payload: dict):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=900) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        return e.code, json.loads(body) if body else {}


def serve_gmres(executor, reqs, label: str):
    """Serve the GMRES-IR requests over HTTP; returns (server, front
    door, results in request order)."""
    task = GMRESIRTask(ir_cfg=IRConfig(), bucket_step=128, min_bucket=128,
                       executor=executor)
    srv = build_server(task, [r["system"] for r in reqs], BUCKETS, SEED)
    fd = serve_http(srv, cfg=HttpConfig(max_n=max(BUCKETS),
                                        sync_timeout_s=900))
    print(f"{label}: boot_to_ready_s={time.perf_counter() - T0:.3f} "
          f"warmup_s={srv.warmup.seconds:.3f} "
          f"compiles={executor_compile_count()} "
          f"cache={aot.cache_stats()}", flush=True)

    def one(r):
        s = r["system"]
        code, body = post(fd.url + "/v1/solve:sync",
                          {"A": s.A.tolist(), "b": s.b.tolist(),
                           "x_true": s.x_true.tolist()})
        return code, body, time.perf_counter()

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(reqs)) as pool:
        results = list(pool.map(one, reqs))
    for bucket in BUCKETS:
        done = [t for (_, _, t), r in zip(results, reqs)
                if r["bucket"] == bucket]
        print(f"{label}: bucket {bucket} served {len(done)} requests, "
              f"last answer after {max(done) - t0:.3f} s "
              "(smoke timing, not a metric)", flush=True)
    return task, srv, fd, results


def check_served(label, task, srv, fd, reqs, results) -> None:
    codes = Counter((code, body.get("status")) for code, body, _ in results)
    check(f"{label}/http", set(codes) == {(200, "done")}, str(dict(codes)))
    check(f"{label}/flush_restarts", fd.flush_restarts == 0,
          f"repro_http_flush_restarts_total={fd.flush_restarts}")
    rep = srv.warmup
    check(f"{label}/warmup", not rep.errors and set(rep.warmed) ==
          set(BUCKETS), f"warmed={sorted(rep.warmed)} errors={rep.errors}")
    check(f"{label}/backend", isinstance(task.backend, PallasBackend)
          and not task.backend.interpret,
          f"{task.backend} (compiled; no fallback exists)")
    lu_kp = -(-task.ir_cfg.blocking.lu_block // LANE) * LANE
    check(f"{label}/no_oracle_route",
          max(BUCKETS) <= MAX_N and lu_kp <= QGEMM_MAX_KP,
          f"largest bucket {max(BUCKETS)} <= trisolve MAX_N {MAX_N}; "
          f"LU panel K {lu_kp} <= QGEMM_MAX_KP {QGEMM_MAX_KP}")
    for bucket in BUCKETS:
        counts = kernel_counts(task, bucket)
        want = BLOCKED_KERNELS if task.ir_cfg.blocking.use_blocked(
            bucket) else STRICT_KERNELS
        check(f"{label}/kernels@{bucket}", want <= set(counts),
              f"tpu_custom_call by kernel: {dict(sorted(counts.items()))}")
    occupancy = dict(srv.telemetry.batches_per_bucket)
    print(f"{label}: batches per bucket {occupancy}", flush=True)
    check_bounds(label, reqs, [body for _, body, _ in results])


def check_bounds(label, reqs, bodies) -> None:
    converged = Counter()
    bad = []
    for r, body in zip(reqs, bodies):
        out = body.get("outcome", {})
        if out.get("status") != CONVERGED:
            continue
        converged[r["bucket"]] += 1
        ferr_b, nbe_b = error_bounds(body["action_names"], r["system"].n,
                                     r["kappa_inf"])
        if not (out["ferr"] <= ferr_b and out["nbe"] <= nbe_b):
            bad.append((body["request_id"], body["action_names"],
                        out["ferr"], ferr_b, out["nbe"], nbe_b))
    statuses = Counter(b.get("outcome", {}).get("status") for b in bodies)
    check(f"{label}/converged", sum(converged.values()) >= 1,
          f"converged per bucket {dict(converged)}; statuses "
          f"{dict(statuses)}")
    check(f"{label}/error_bounds", not bad,
          f"{sum(converged.values())} converged rows checked"
          + (f"; outside: {bad}" if bad else ""))


def cpu_cross_check(reqs, bodies, task) -> None:
    """Re-solve the first request of each bucket on the host CPU with the
    jnp backend on the chip's f32 carrier, under the action the server
    chose, and compare with what the chip answered."""
    cpu = jax.devices("cpu")[0]
    ref = JnpBackend(carrier_dtype="float32")
    space = task.action_space
    rows = []
    for bucket in BUCKETS:
        i = next(k for k, r in enumerate(reqs) if r["bucket"] == bucket)
        r, body = reqs[i], bodies[i]
        A, b, x = task.prepare(r["system"])
        act = np.asarray(space.actions[body["action"]], np.int32)
        with jax.default_device(cpu):
            st = gmres_ir_batch(A[None], b[None], x[None], act[None],
                                task.ir_cfg, backend=ref)
        host = {"status": int(st.status[0]), "ferr": float(st.ferr[0]),
                "nbe": float(st.nbe[0])}
        chip = body["outcome"]
        bounds = error_bounds(body["action_names"], r["system"].n,
                              r["kappa_inf"])
        ok = agree(chip, host, bounds)
        rows.append(ok)
        print(f"cpu_cross_check bucket {bucket} action "
              f"{tuple(body['action_names'])}: chip status/ferr/nbe "
              f"{chip['status']}/{chip['ferr']:.3e}/{chip['nbe']:.3e}, "
              f"cpu {host['status']}/{host['ferr']:.3e}/{host['nbe']:.3e},"
              f" bounds {bounds[0]:.3e}/{bounds[1]:.3e}: "
              f"{'agree' if ok else 'DISAGREE'}", flush=True)
    check("gmres/cpu_cross_check", all(rows),
          f"{sum(rows)}/{len(rows)} rows agree")


def run_cg(rng) -> None:
    """A few CG-IR solves on seeded SPD systems through the same server
    stack. kappa 1e1-1e3, so that an f32 carrier can resolve them (the
    paper's Table 4 set, kappa 1e8-1e10, is beyond kappa * 2^-24 < 1)."""
    systems = [sparse_spd(n, 0.01, rng, 10.0 ** rng.uniform(1.0, 3.0))
               for n in CG_SIZES]
    task = CGIRTask(bucket_step=128, min_bucket=128)
    buckets = sorted({task.bucket_key(s) for s in systems})
    srv = build_server(task, systems, buckets, SEED + 1)
    t0 = time.perf_counter()
    ids = [srv.submit(s) for s in systems]
    srv.drain()
    resps = [srv.poll(i) for i in ids]
    print(f"cg: {len(systems)} solves in {time.perf_counter() - t0:.3f} s "
          "(smoke timing, not a metric)", flush=True)
    check("cg/answered", all(r is not None for r in resps))
    check("cg/warmup", not srv.warmup.errors, str(srv.warmup.errors))
    for bucket in buckets:
        counts = kernel_counts(task, bucket)
        want = BLOCKED_KERNELS if task.cg_cfg.blocking.use_blocked(
            bucket) else STRICT_KERNELS
        check(f"cg/kernels@{bucket}", want <= set(counts),
              f"tpu_custom_call by kernel: {dict(sorted(counts.items()))}")
    reqs = [{"bucket": task.bucket_key(s), "system": s,
             "kappa_inf": float(np.linalg.cond(s.A, np.inf))}
            for s in systems]
    bodies = [{"request_id": r.request_id, "action_names":
               list(r.action_names),
               "outcome": {"status": int(r.record.status),
                           **r.record.metrics}} for r in resps]
    check_bounds("cg", reqs, bodies)


def run_one_chip(reqs, rng) -> None:
    task, srv, fd, results = serve_gmres(None, reqs, "gmres")
    try:
        check_served("gmres", task, srv, fd, reqs, results)
        cpu_cross_check(reqs, [body for _, body, _ in results], task)
    finally:
        fd.close()
    run_cg(rng)


def run_four_chips(reqs) -> None:
    """The ShardedExecutor serving path, and LocalExecutor on one of the
    same chips as its reference. DESIGN.md §7.3 promises bit-equal rows;
    on a TPU it does not fully hold: the per-device program (2 rows)
    and the local one (8 rows) are compiled apart, and a few rows come
    out different in their low bits. The count of bit-equal rows is
    printed; every row must `agree`."""
    task, srv, fd, results = serve_gmres(ShardedExecutor(data=4), reqs,
                                         "sharded")
    try:
        check_served("sharded", task, srv, fd, reqs, results)
    finally:
        fd.close()
    local = GMRESIRTask(ir_cfg=task.ir_cfg, bucket_step=128, min_bucket=128,
                        action_space=task.action_space,
                        executor=LocalExecutor())
    bodies = [body for _, body, _ in results]
    exact = close = total = 0
    for bucket in BUCKETS:
        idx = [k for k, r in enumerate(reqs) if r["bucket"] == bucket]
        rows = [local.prepare(reqs[k]["system"]) for k in idx]
        acts = [local.action_space.actions[bodies[k]["action"]]
                for k in idx]
        for k, out in zip(idx, local.solve_rows(rows, acts, MAX_BATCH)):
            body, total = bodies[k], total + 1
            mine = {"status": int(out.status), **out.metrics}
            theirs = body["outcome"]
            same = all(mine[key] == theirs[key] for key in
                       ("status", "ferr", "nbe", "n_outer", "n_gmres",
                        "res_norm"))
            exact += same
            close += same or agree(theirs, mine, error_bounds(
                body["action_names"], reqs[k]["system"].n,
                reqs[k]["kappa_inf"]))
    print(f"sharded_vs_local: {exact}/{total} rows bit-equal, "
          f"{close}/{total} within the agree() tolerance", flush=True)
    check("sharded_vs_local", close == total,
          f"{exact}/{total} bit-equal (DESIGN.md §7.3)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices; JAX found {len(jax.devices())}")
    rng = np.random.default_rng(SEED)
    reqs = make_requests(rng)
    print(f"requests: {len(reqs)} dense randsvd systems, n "
          f"{min(r['system'].n for r in reqs)}-"
          f"{max(r['system'].n for r in reqs)}, buckets {BUCKETS}",
          flush=True)
    if args.chips == 4:
        run_four_chips(reqs)
    else:
        run_one_chip(reqs, rng)
    print(f"compiles={executor_compile_count()} cache={aot.cache_stats()} "
          f"total_s={time.perf_counter() - T0:.3f}", flush=True)
    if FAILURES:
        print(f"chip_smoke: failed checks: {FAILURES}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
