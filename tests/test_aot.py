"""AOT bucket-grid warmup + persistent compile cache (DESIGN.md §12).

In-process tests cover the pure planning layer (traffic-priority bucket
order, grid enumeration), warm-equals-cold bit-identity, executable
dedupe across tasks, warmup no-ops on already-warm engines, and the two
server warmup modes: ``warmup="sync"`` must make the first live request
compile-free, ``warmup="background"`` must flip the `/readyz` warm gate
per bucket in priority order while traffic is already flowing.

The persistent-cache contract — a restarted server rebuilds its grid
from ``JAX_COMPILATION_CACHE_DIR`` with ZERO fresh XLA compiles — runs as
two subprocess boots sharing one cache directory, asserted on the
jax compilation-cache hit/miss counters (never on wall time).
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import (Discretizer, LocalExecutor, QTable, computation_key,
                        reduced_action_space)
from repro.core import aot
from repro.core import executor as EX
from repro.core.engine import AutotuneEngine
from repro.core.executor import batch_callable
from repro.core.features import PAPER_FEATURES
from repro.core.policy import PrecisionPolicy
from repro.data import generate_dense_set
from repro.data.matrices import randsvd_dense
from repro.obs import Observability
from repro.service import AutotuneServer, BatcherConfig
from repro.solvers import IRConfig, gmres_ir_batch_lowerable
from repro.tasks import GMRESIRTask
from repro.tasks.base import stack_fixed

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SPACE = reduced_action_space()
BCFG = BatcherConfig(max_batch=2, max_wait_s=0.001, bucket_step=16,
                     min_bucket=16)

# Every compiling test uses its own tau so its grid cells are genuinely
# cold — the per-shape executable caches are process-global.


def _ir(tau):
    return IRConfig(tau=tau, i_max=4, m_max=12)


def _policy():
    nf = len(PAPER_FEATURES)
    feats = np.random.default_rng(0).normal(size=(8, nf))
    disc = Discretizer.fit(feats, [2] * nf)
    return PrecisionPolicy(SPACE, disc,
                           QTable(disc.n_states, SPACE.n_actions))


def _systems(k, seed=0):
    return generate_dense_set(k, np.random.default_rng(seed),
                              n_range=(12, 14),
                              log10_kappa_range=(3, 4))


def _readyz(url):
    try:
        with urllib.request.urlopen(url + "/readyz", timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# Planning layer: traffic priority + grid enumeration (pure, no jax)
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_order_buckets_traffic_priority(tmp_path):
    # No traffic: smallest first (fastest compiles flip /readyz first).
    assert aot.order_buckets([48, 16, 32]) == [16, 32, 48]
    # Most-seen first; size breaks ties.
    assert aot.order_buckets([16, 32, 48],
                             traffic={32: 5, 48: 5}) == [32, 48, 16]
    # Trajectory-log counts add onto explicit traffic.
    p = tmp_path / "traj.jsonl"
    rows = [{"bucket": 48}] * 3 + [{"bucket": 16}, {"other": 1}]
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\nnot json\n")
    assert aot.order_buckets([16, 32, 48],
                             trajectory_path=str(p)) == [48, 16, 32]
    # Fail-open: unreadable path reads as no traffic.
    assert aot.bucket_traffic(str(tmp_path / "missing.jsonl")) == {}
    assert aot.bucket_traffic(None) == {}


@pytest.mark.fast
def test_plan_enumerates_tasks_per_bucket_in_priority_order():
    t1, t2 = object(), object()
    entries = aot.plan([t1, t2], [32, 16], chunk=4, traffic={32: 9})
    assert [(e.task, e.bucket, e.chunk) for e in entries] == [
        (t1, 32, 4), (t2, 32, 4), (t1, 16, 4), (t2, 16, 4)]
    labels = entries[0].labels()
    assert set(labels) == {"task", "bucket", "backend", "executor"}
    assert labels["bucket"] == 32


@pytest.mark.fast
def test_enable_persistent_cache_noop_without_dir(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at the fixed
    <checkout>/.cache/xla — never a temporary, per-process or per-run
    path — and enabling it again changes nothing."""
    monkeypatch.delenv(aot.ENV_CACHE_DIR, raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".cache", "xla")
    assert aot.default_cache_dir() == want
    assert aot.enable_persistent_cache() == want
    assert aot.enable_persistent_cache() == aot.cache_stats()["dir"] == want


# ---------------------------------------------------------------------------
# Warm == cold bit-identity, dedupe, warm-engine no-op
# ---------------------------------------------------------------------------

def test_aot_executable_bitmatches_plain_dispatch():
    """The dispatcher's AOT-compiled route must be bit-identical to the
    plain jitted call — same entry point, same coercion, same shapes."""
    import jax
    cfg = _ir(2.5e-6)
    low = gmres_ir_batch_lowerable(cfg)
    rng = np.random.default_rng(3)
    from repro.core import pad_to_bucket
    row = pad_to_bucket(randsvd_dense(13, 1e3, rng), 16, 16)
    act = np.asarray(SPACE.actions[5], np.int32)
    A, b, x, acts, _ = stack_fixed([row, row], [act, act], 2)
    ref = low(A, b, x, acts)                       # plain jit dispatch
    got = LocalExecutor().dispatch(low, (A, b, x, acts), 16)  # AOT cache
    for rl, gl in zip(jax.tree_util.tree_leaves(ref),
                      jax.tree_util.tree_leaves(got)):
        assert np.asarray(rl).tobytes() == np.asarray(gl).tobytes()


def test_cross_task_precompile_shares_one_executable():
    """Two tasks over the same (config, backend, executor) collapse onto
    one dispatcher and one executable per shape (DESIGN.md §12)."""
    cfg = _ir(3.5e-6)
    t1 = GMRESIRTask(_systems(1, seed=1), SPACE, cfg, bucket_step=16,
                     min_bucket=16)
    t2 = GMRESIRTask(_systems(1, seed=2), SPACE, cfg, bucket_step=16,
                     min_bucket=16)
    assert computation_key(t1.lowerable_for(16)) == \
        computation_key(t2.lowerable_for(16))
    c0 = EX.executor_compile_count()
    assert t1.precompile_bucket(16, 2)
    assert EX.executor_compile_count() == c0 + 1
    assert t2.precompile_bucket(16, 2)            # dedupe: no new build
    assert EX.executor_compile_count() == c0 + 1
    wrapped = batch_callable(LocalExecutor(), None, t1.lowerable_for(16))
    assert len(wrapped.executables) == 1
    assert batch_callable(LocalExecutor(), None,
                          t2.lowerable_for(16)) is wrapped


def test_engine_precompile_noop_when_already_warm():
    """Warming an engine that already solved its buckets builds nothing:
    the live path and the warmup path share the per-shape cache."""
    cfg = _ir(4.5e-6)
    task = GMRESIRTask(_systems(2, seed=3), SPACE, cfg, bucket_step=16,
                       min_bucket=16)
    eng = AutotuneEngine(task, chunk=2)
    eng.solve_pairs([(0, 0), (1, 0)])
    c0 = EX.executor_compile_count()
    out = eng.precompile()
    assert out == [(16, True)]
    assert EX.executor_compile_count() == c0      # nothing new to build


# ---------------------------------------------------------------------------
# Server warmup modes
# ---------------------------------------------------------------------------

def test_sync_warmup_first_request_hits_warm_executable():
    """``warmup="sync"``: ready pre-traffic, and the first live request
    records zero compiles and zero wrap builds — the cliff is gone."""
    srv = AutotuneServer(_policy(), _ir(5.5e-6), batcher_cfg=BCFG,
                         obs=False, seed=0, warmup="sync",
                         warmup_buckets=[12, 28])
    assert sorted(srv._warmup_expected) == [16, 32]   # sizes -> buckets
    assert srv.ready                                  # before any traffic
    state = srv.warmup_state()
    assert state["mode"] == "sync" and state["done"]
    assert state["warmed_buckets"] == [16, 32]
    c0, w0 = EX.executor_compile_count(), len(EX._WRAPPED)
    for s in _systems(2, seed=4):
        srv.submit(s)
    srv.drain()
    assert EX.executor_compile_count() == c0          # zero compiles
    assert len(EX._WRAPPED) == w0                     # zero wrap builds
    assert srv.telemetry.snapshot()["n_solves"] == 2


def test_background_warmup_flips_readyz_per_bucket_in_priority_order(
        tmp_path):
    """``warmup="background"``: /readyz starts 503 with the grid
    pending, flips warm per bucket in trajectory-traffic order, and
    goes 200 exactly when the expected grid is warm."""
    traj = tmp_path / "traj.jsonl"
    traj.write_text("".join(json.dumps({"bucket": b}) + "\n"
                            for b in (32, 32, 32, 16)))
    gate = threading.Semaphore(0)
    srv = AutotuneServer(_policy(), _ir(6.5e-6), batcher_cfg=BCFG, seed=0,
                         obs=Observability(trajectory_path=str(traj)),
                         warmup="background", warmup_buckets=[16, 32],
                         warmup_pace=lambda e: gate.acquire())
    http = srv.serve_obs()
    try:
        code, body = _readyz(http.url)
        assert code == 503
        assert body["warmup"]["pending_buckets"] == [16, 32]
        assert not srv.ready
        gate.release()                       # let bucket #1 compile
        while len(srv.warm_order) < 1:
            time.sleep(0.05)
        code, body = _readyz(http.url)
        assert code == 503                   # 32 warm, 16 still pending
        assert body["warmup"]["warmed_buckets"] == [32]
        gate.release()                       # let bucket #2 compile
        assert srv.warmup.wait(120).done
        code, body = _readyz(http.url)
        assert code == 200
        assert body["warmup"]["done"]
        assert srv.warm_order == [32, 16]    # trajlog priority held
        assert srv.ready
    finally:
        http.close()


# ---------------------------------------------------------------------------
# Warm restart: disk cache serves the whole grid (subprocess x2)
# ---------------------------------------------------------------------------

WARM_BOOT = r"""
import jax
jax.config.update("jax_enable_x64", True)
import json, time, urllib.error, urllib.request
import numpy as np
from repro.core import (Discretizer, QTable, reduced_action_space)
from repro.core import aot, executor as EX
from repro.core.features import PAPER_FEATURES
from repro.core.policy import PrecisionPolicy
from repro.data import generate_dense_set
from repro.obs import Observability
from repro.service import AutotuneServer, BatcherConfig
from repro.solvers import IRConfig

SPACE = reduced_action_space()
nf = len(PAPER_FEATURES)
feats = np.random.default_rng(0).normal(size=(8, nf))
disc = Discretizer.fit(feats, [2] * nf)
pol = PrecisionPolicy(SPACE, disc, QTable(disc.n_states, SPACE.n_actions))
srv = AutotuneServer(pol, IRConfig(tau=8.5e-6, i_max=4, m_max=12),
                     batcher_cfg=BatcherConfig(max_batch=2,
                                               max_wait_s=0.001,
                                               bucket_step=16,
                                               min_bucket=16),
                     obs=Observability(), seed=0, warmup="background",
                     warmup_buckets=[16])   # cache dir via env
http = srv.serve_obs()
deadline, ready = time.time() + 300, None
while time.time() < deadline:          # wait for the warm gate
    try:
        with urllib.request.urlopen(http.url + "/readyz",
                                    timeout=10) as r:
            ready = r.status
            break
    except urllib.error.HTTPError:     # 503: grid still compiling
        time.sleep(0.2)
assert srv.warmup.wait(300).done
s = generate_dense_set(1, np.random.default_rng(7), n_range=(12, 14),
                       log10_kappa_range=(3, 4))
rid = srv.submit(s[0])
srv.drain()
resp = srv.poll(rid)
http.close()
print("RESULT " + json.dumps({
    "ready": ready,
    "compiles": EX.executor_compile_count(),
    "cache": aot.cache_stats(),
    "digest": {"action": int(resp.action),
               "status": int(resp.record.status),
               "metrics": {k: repr(v)
                           for k, v in sorted(
                               resp.record.metrics.items())}}}))
"""


def test_warm_restart_zero_fresh_xla_compiles(tmp_path):
    """Two boots sharing one JAX_COMPILATION_CACHE_DIR: the restart must
    rebuild its grid purely from disk — zero compile-cache misses,
    asserted on counters, never timing — and solve bit-identically."""
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", WARM_BOOT], env=env,
                             capture_output=True, text=True, timeout=600)
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        assert lines, (out.stdout[-2000:], out.stderr[-3000:])
        runs.append(json.loads(lines[-1][len("RESULT "):]))
    first, second = runs
    assert first["ready"] == 200 and second["ready"] == 200
    assert first["cache"]["dir"] == str(tmp_path / "xla-cache")
    assert second["cache"]["dir"] == first["cache"]["dir"]
    # Cold boot really compiled; warm restart did zero fresh XLA work.
    assert first["cache"]["misses"] > 0, first
    assert second["cache"]["misses"] == 0, second
    assert second["cache"]["hits"] > 0, second
    # Same number of in-process executable builds either way (the cache
    # serves the XLA work, not the dispatcher bookkeeping)...
    assert second["compiles"] == first["compiles"]
    # ...and the restart is bit-stable end to end.
    assert second["digest"] == first["digest"]
