"""Cold-start benchmark: the compile cliff, measured (DESIGN.md §12).

Three serving arms, each booted in a fresh subprocess (a fresh process
is the only honest "cold": jit caches, dispatcher memos, and the
per-shape executable caches are all process-global):

  cold         — lazy server, no warmup, an empty compile cache: the
                 first request per bucket pays lower+compile in-band.
  warmed       — ``warmup="sync"`` over the bucket grid, an empty compile
                 cache: compiles run at boot, the first request
                 dispatches a warm executable.
  disk_restart — ``warmup="sync"``; the arm is the SECOND boot against
                 the same ``JAX_COMPILATION_CACHE_DIR``, so its warmup is
                 served from disk (zero fresh XLA compiles, asserted on
                 the jax compilation-cache counters — never timing).

Each arm's cache directory is a fixed path under ``.cache/cold_start``,
emptied before the arm's first boot.

Per arm, per bucket: first-request latency, then steady-state p50/p99
over repeated single-request round trips; plus boot-to-ready and
boot-to-first-solve walls. The headline derived number is
``first/steady-p50`` — the cliff ratio the warmup is meant to kill.

All arms run single-request micro-batches; the report names the device
the arms ran on, and is about *relative* first-hit vs steady-state shape
(honest-labeling rule, DESIGN.md §10).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from benchmarks.common import save_report

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO_ROOT, "src")
CACHE_ROOT = os.path.join(REPO_ROOT, ".cache", "cold_start")

# Child process: boot one serving arm, time first hits + steady state.
# `_T0` is bound before any heavy import so boot walls include them.
CHILD = r"""
import time
_T0 = time.time()
import json, sys
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core import Discretizer, QTable, reduced_action_space
from repro.core import aot, executor as EX
from repro.core.features import PAPER_FEATURES
from repro.core.policy import PrecisionPolicy
from repro.data import generate_dense_set
from repro.service import AutotuneServer, BatcherConfig
from repro.solvers import IRConfig

arm, steady_n = sys.argv[1], int(sys.argv[2])
SPACE = reduced_action_space()
nf = len(PAPER_FEATURES)
feats = np.random.default_rng(0).normal(size=(8, nf))
disc = Discretizer.fit(feats, [2] * nf)
pol = PrecisionPolicy(SPACE, disc, QTable(disc.n_states, SPACE.n_actions))
warm = dict(warmup="sync", warmup_buckets=[16, 32]) \
    if arm != "cold" else {}
srv = AutotuneServer(pol, IRConfig(tau=1e-5, i_max=4, m_max=12),
                     batcher_cfg=BatcherConfig(max_batch=1,
                                               max_wait_s=0.0,
                                               bucket_step=16,
                                               min_bucket=16),
                     obs=False, seed=0, **warm)
t_ready = time.time() - _T0

def solve_one(n_lo, n_hi, seed):
    s = generate_dense_set(1, np.random.default_rng(seed),
                           n_range=(n_lo, n_hi),
                           log10_kappa_range=(3, 4))[0]
    t0 = time.perf_counter()
    rid = srv.submit(s)
    srv.drain()
    assert srv.poll(rid) is not None
    return time.perf_counter() - t0

out = {"arm": arm, "boot_to_ready_s": round(t_ready, 3), "buckets": {}}
first_solve_done = None
for bucket, (lo, hi) in ((16, (12, 15)), (32, (20, 30))):
    first = solve_one(lo, hi, 100 + bucket)
    if first_solve_done is None:
        first_solve_done = time.time() - _T0
    lats = sorted(solve_one(lo, hi, 1000 + bucket + i)
                  for i in range(steady_n))
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    out["buckets"][str(bucket)] = {
        "first_request_s": round(first, 4),
        "steady_p50_s": round(p50, 4),
        "steady_p99_s": round(p99, 4),
        "first_over_steady_p50": round(first / p50, 1),
        "n_steady": len(lats)}
out["boot_to_first_solve_s"] = round(first_solve_done, 3)
out["executor_compiles"] = EX.executor_compile_count()
out["compile_cache"] = aot.cache_stats()
dev = jax.devices()[0]
out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                 "count": jax.device_count()}
print("RESULT " + json.dumps(out))
"""


def _boot(arm: str, steady_n: int, fresh: bool = True) -> dict:
    """One arm's boot in a child process, against the arm's fixed cache
    directory (emptied first when `fresh`)."""
    cache_dir = os.path.join(CACHE_ROOT, arm)
    if fresh:
        shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    out = subprocess.run(
        [sys.executable, "-c", CHILD, arm, str(steady_n)],
        env=env, capture_output=True, text=True, timeout=1200)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("RESULT ")]
    if not lines:
        raise RuntimeError(
            f"cold_start arm {arm!r} produced no result: "
            f"{out.stdout[-1000:]} {out.stderr[-2000:]}")
    return json.loads(lines[-1][len("RESULT "):])


def run(full: bool = False, steady_n: int = None):
    steady_n = steady_n or (50 if full else 25)
    report = {"steady_n": steady_n, "arms": {}}
    report["arms"]["cold"] = _boot("cold", steady_n)
    report["arms"]["warmed"] = _boot("warmed", steady_n)
    priming = _boot("disk_restart", steady_n)
    restart = _boot("disk_restart", steady_n, fresh=False)
    restart["priming_boot_to_ready_s"] = priming["boot_to_ready_s"]
    report["arms"]["disk_restart"] = restart
    # Counter-based warm-restart proof: the second boot's entire grid
    # came from disk (hits > 0) with zero fresh XLA compiles.
    report["warm_restart_zero_fresh_compiles"] = bool(
        restart["compile_cache"]["misses"] == 0
        and restart["compile_cache"]["hits"] > 0)
    dev = restart["device"]
    report["device"] = dev
    report["note"] = (f"{dev['platform']} ({dev['kind']}); relative "
                      "first-hit vs steady-state shape")
    save_report("cold_start", report)
    rows = []
    for arm, data in report["arms"].items():
        for bucket, b in data["buckets"].items():
            rows.append(
                f"cold_start/{arm}/bucket{bucket},"
                f"{b['first_request_s'] * 1e6:.0f},"
                f"p50={b['steady_p50_s']:.4f}s;"
                f"p99={b['steady_p99_s']:.4f}s;"
                f"cliff={b['first_over_steady_p50']:.1f}x")
        rows.append(f"cold_start/{arm}/boot,"
                    f"{data['boot_to_first_solve_s'] * 1e6:.0f},"
                    f"ready={data['boot_to_ready_s']:.1f}s;"
                    f"compiles={data['executor_compiles']}")
    return rows
