"""Execution layer (DESIGN.md §7): executor contract + sharded parity.

In-process tests cover the pure-Python contract (chunk rounding,
registry/selection, engine/batcher integration) and the degenerate
1-device mesh, which must bit-match the local path anywhere.

The heavy parity suite runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (device count is
fixed at jax import, same pattern as tests/test_distributed.py):
`ShardedExecutor` SolveRecords must bit-match `LocalExecutor` for all 7
format ids, single and batched rows, strict and blocked factorization
paths, end-to-end through the `AutotuneEngine` and the serving stack,
with one executable per bucket across a full precision-action sweep.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (LocalExecutor, ShardedExecutor, pad_to_bucket,
                        reduced_action_space, resolve_executor,
                        solve_fixed_batch)
from repro.core.engine import AutotuneEngine
from repro.data.matrices import randsvd_dense
from repro.service import AutotuneServer, BatcherConfig, MicroBatcher
from repro.solvers import IRConfig
from repro.tasks import GMRESIRTask

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SPACE = reduced_action_space()
IR = IRConfig(tau=1e-5, i_max=4, m_max=12)


# ---------------------------------------------------------------------------
# Contract: chunk rounding, registry, selection
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_preferred_chunk_rounding():
    assert LocalExecutor().preferred_chunk(9) == 9
    ex = ShardedExecutor(data=4)
    assert ex.preferred_chunk(1) == 4    # at least one row per device
    assert ex.preferred_chunk(3) == 4
    assert ex.preferred_chunk(4) == 4
    assert ex.preferred_chunk(8) == 8
    assert ex.preferred_chunk(9) == 12   # round UP, never down
    # Rounding depends only on the request, never on queue occupancy:
    # that is what keeps the compiled shape stable per bucket.
    assert ex.preferred_chunk(8, bucket=128) == 8


@pytest.mark.fast
def test_registry_and_selection(monkeypatch):
    """What selects an executor: a name, an instance, else the
    operator's `REPRO_SOLVE_EXECUTOR`, else "local"; the precision
    backend is the platform's unless named."""
    import jax

    from repro.core import executor as E
    from repro.precision import PallasBackend, resolve_backend
    monkeypatch.delenv(E.ENV_VAR, raising=False)
    assert resolve_executor(None) == LocalExecutor()
    assert resolve_executor("local") == LocalExecutor()
    assert resolve_executor("sharded") == ShardedExecutor()
    inst = ShardedExecutor(data=1)
    assert resolve_executor(inst) is inst
    with pytest.raises(KeyError, match="nope"):
        resolve_executor("nope")
    monkeypatch.setenv(E.ENV_VAR, "sharded")
    assert resolve_executor(None).name == "sharded"
    assert resolve_executor("local").name == "local"    # a name beats it
    assert resolve_executor(inst) is inst
    monkeypatch.setenv(E.ENV_VAR, "nope")
    with pytest.raises(KeyError, match="nope"):
        resolve_executor(None)
    # The platform chooses the precision backend, whatever the env says.
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_backend(None).name == "jnp"
    with pytest.raises(RuntimeError, match="pallas-interpret"):
        resolve_backend("pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend(None) == PallasBackend()


@pytest.mark.fast
def test_executors_hash_by_value():
    """Equal-valued executors must share memoized dispatch wrappers
    (and therefore compiled executables)."""
    assert LocalExecutor() == LocalExecutor()
    assert hash(ShardedExecutor(data=2)) == hash(ShardedExecutor(data=2))
    assert ShardedExecutor(data=2) != ShardedExecutor(data=4)


@pytest.mark.fast
def test_mesh_larger_than_host_raises():
    import jax
    ndev = jax.device_count()
    with pytest.raises(ValueError, match="devices"):
        ShardedExecutor(data=ndev * 64).mesh()


# ---------------------------------------------------------------------------
# Degenerate 1-device mesh == local, bitwise
# ---------------------------------------------------------------------------

def test_one_device_mesh_bitmatches_local():
    rng = np.random.default_rng(2)
    rows = [pad_to_bucket(randsvd_dense(int(n), 1e3, rng), 16, 16)
            for n in (13, 10, 12)]
    acts = [SPACE.actions[i] for i in (0, 20, SPACE.n_actions - 1)]
    loc = solve_fixed_batch([r[0] for r in rows], [r[1] for r in rows],
                            [r[2] for r in rows], acts, IR, chunk=4)
    sh = solve_fixed_batch([r[0] for r in rows], [r[1] for r in rows],
                           [r[2] for r in rows], acts, IR, chunk=4,
                           executor=ShardedExecutor(data=1))
    for a, b in zip(loc, sh):
        assert a.__dict__ == b.__dict__


# ---------------------------------------------------------------------------
# Engine + batcher integration via a stub executor (no extra devices)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FourRounder(LocalExecutor):
    """Local dispatch with a mesh-like granularity of 4 — exercises the
    chunk-rounding plumbing without needing multiple devices."""
    name: str = dataclasses.field(default="four", init=False)

    def preferred_chunk(self, chunk: int, bucket: int = 0) -> int:
        return max(4, -(-int(chunk) // 4) * 4)

    def device_count(self) -> int:
        return 4


def _systems(k, seed=0, lo=9, hi=14):
    rng = np.random.default_rng(seed)
    return [randsvd_dense(int(n), 100.0, rng)
            for n in rng.integers(lo, hi, size=k)]


def test_engine_rounds_chunk_and_accounts_padding():
    task = GMRESIRTask(_systems(3, seed=1), SPACE, IR, bucket_step=16,
                       min_bucket=16, executor=FourRounder())
    eng = AutotuneEngine(task, chunk=2)          # rounds up to 4
    assert eng.executor == FourRounder()         # picked up from the task
    eng.solve_pairs([(i, 0) for i in range(3)])
    assert eng.n_solves == 3
    assert eng.n_pad_solves == 1                 # 4-row chunk, 3 live rows
    summ = eng.summarize()
    assert summ["n_devices"] == 4
    assert summ["rows_per_device"] == 1
    assert summ["n_solves_per_device"] == pytest.approx(3 / 4)


def test_batcher_flush_targets_executor_chunk():
    task = GMRESIRTask((), SPACE, IR, bucket_step=16, min_bucket=16,
                       executor=FourRounder())
    mb = MicroBatcher(task, BatcherConfig(max_batch=3, max_wait_s=1e9,
                                          bucket_step=16, min_bucket=16))
    assert mb.flush_target(16) == 4              # max_batch rounded up
    for s in _systems(3, seed=2, lo=9, hi=14):
        mb.submit(s, SPACE.actions[-1])
    assert mb.pump() == []                       # 3 < flush target of 4
    mb.submit(_systems(1, seed=3)[0], SPACE.actions[-1])
    out = mb.pump()
    assert len(out) == 1
    assert out[0].n_rows == 4                    # rows solved == target
    assert len(out[0].records) == 4


def test_server_threads_executor_to_task_and_telemetry():
    from repro.core import QTable, Discretizer, W1
    from repro.core.policy import PrecisionPolicy
    feats = np.array([[1.0, 10.0], [5.0, 1e4]])
    disc = Discretizer.fit(feats, (2, 2))
    snap = PrecisionPolicy(SPACE, disc, QTable(disc.n_states,
                                               SPACE.n_actions))
    srv = AutotuneServer(snap, IR,
                         batcher_cfg=BatcherConfig(max_batch=2,
                                                   max_wait_s=1e9,
                                                   bucket_step=16,
                                                   min_bucket=16),
                         executor=FourRounder())
    assert srv.executor == FourRounder()
    assert srv.task.executor == FourRounder()    # legacy cfg adapted with it
    assert srv.batcher.flush_target(16) == 4
    for s in _systems(4, seed=4):
        srv.submit(s)
    srv.drain()
    tel = srv.telemetry.snapshot()
    # Pad accounting reflects the executor's 4-row granularity.
    assert tel["solver_rows"] % 4 == 0
    assert tel["n_solves"] == 4
    assert tel["n_solves"] + tel["n_pad_solves"] == tel["solver_rows"]


def test_records_from_stats_single_host_transfer(monkeypatch):
    """The whole SolveStats tuple must come to host in ONE device_get."""
    import jax
    from repro.core import batching
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(batching.jax, "device_get", counting)
    rng = np.random.default_rng(5)
    A, b, x = pad_to_bucket(randsvd_dense(11, 10.0, rng), 16, 16)
    (rec,) = solve_fixed_batch([A], [b], [x], [SPACE.actions[-1]], IR,
                               chunk=2)
    assert len(calls) == 1
    assert rec.status in (0, 1, 2, 3)


# ---------------------------------------------------------------------------
# 8-device host mesh: the full parity + accounting suite (subprocess)
# ---------------------------------------------------------------------------

PARITY_8DEV = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core import (LocalExecutor, ShardedExecutor, pad_to_bucket,
                        reduced_action_space, solve_fixed_batch)
from repro.core import executor as EX
from repro.core.engine import AutotuneEngine
from repro.data.matrices import randsvd_dense, sparse_spd
from repro.solvers import BlockingPolicy, CGConfig, IRConfig
from repro.tasks import CGIRTask, GMRESIRTask

assert jax.device_count() == 8, jax.device_count()
SPACE = reduced_action_space()
IR = IRConfig(tau=1e-5, i_max=4, m_max=12)
CG = CGConfig(tau=1e-5, i_max=4, m_max=12)
# Threshold-lowered blocking so the small parity systems exercise the
# blocked LU + trisolve path end to end (DESIGN.md §6.4).
IRB = IRConfig(tau=1e-5, i_max=4, m_max=12,
               blocking=BlockingPolicy(min_n=16, lu_block=16,
                                       trisolve_block=16))

# --- solve_fixed_batch parity: all 7 format ids, single + batched ---------
for fid in range(7):
    A, b, x = pad_to_bucket(
        randsvd_dense(13, 10.0 ** (fid % 5), np.random.default_rng(fid)),
        16, 16)
    act = np.asarray([fid] * 4, np.int32)
    for cfg in (IR, IRB):
        loc = solve_fixed_batch([A], [b], [x], [act], cfg, chunk=8)
        sh = solve_fixed_batch([A], [b], [x], [act], cfg, chunk=8,
                               executor=ShardedExecutor(data=8))
        assert loc[0].__dict__ == sh[0].__dict__, (fid, cfg, loc, sh)
rows = [pad_to_bucket(randsvd_dense(int(n), 10.0 ** k,
                                    np.random.default_rng(k)), 16, 16)
        for k, n in enumerate((10, 13, 12, 14, 11, 9, 15, 10))]
acts = [SPACE.actions[i % SPACE.n_actions] for i in range(8)]
for d in (2, 4, 8):
    loc = solve_fixed_batch([r[0] for r in rows], [r[1] for r in rows],
                            [r[2] for r in rows], acts, IR, chunk=8)
    sh = solve_fixed_batch([r[0] for r in rows], [r[1] for r in rows],
                           [r[2] for r in rows], acts, IR, chunk=8,
                           executor=ShardedExecutor(data=d))
    for a, b_ in zip(loc, sh):
        assert a.__dict__ == b_.__dict__, d
print("PARITY_BATCH_OK")

# --- engine e2e (both tasks, full action space) + accounting --------------
def engine(cls, systems, cfg, kw, ex, chunk=4):
    t = cls(systems, SPACE, bucket_step=16, min_bucket=16, executor=ex,
            **{kw: cfg})
    e = AutotuneEngine(t, chunk=chunk)
    e.prefill_all()
    return e

dsys = [randsvd_dense(int(n), 10.0 ** (i + 1), np.random.default_rng(i))
        for i, n in enumerate((9, 11, 13, 10))]
ssys = [sparse_spd(int(n), 0.2, np.random.default_rng(i), 1e4)
        for i, n in enumerate((9, 11, 13, 10))]
for cls, systems, cfg, kw in ((GMRESIRTask, dsys, IR, "ir_cfg"),
                              (CGIRTask, ssys, CG, "cg_cfg")):
    el = engine(cls, systems, cfg, kw, None)
    es = engine(cls, systems, cfg, kw, ShardedExecutor(data=8))
    for i in range(len(systems)):
        for a in range(SPACE.n_actions):
            got, want = es.outcome(i, a), el.outcome(i, a)
            assert got.status == want.status, (cls.__name__, i, a)
            assert got.metrics == want.metrics, (cls.__name__, i, a)
    # Chunk rounded 4 -> 8: pad rows are counted, per-device view honest.
    s = es.summarize()
    assert s["n_devices"] == 8
    assert es.n_solves == len(systems) * SPACE.n_actions
    total = es.n_solves + es.n_pad_solves
    assert total % 8 == 0 and s["rows_per_device"] == total // 8
print("PARITY_ENGINE_OK")

# --- recompile accounting: one executable per bucket ----------------------
from repro.core.executor import batch_callable
from repro.solvers import gmres_ir_batch_lowerable
wrapped = batch_callable(ShardedExecutor(data=8), None,
                         gmres_ir_batch_lowerable(IR))
# One bucket, full action sweep already ran through this wrapper above:
# exactly one AOT-compiled executable in the per-shape cache.
assert len(wrapped.executables) == 1, sorted(wrapped.executables)
# An equal-valued executor + equal-valued lowerable reuse the same
# wrapper (computation_key collapses them — no new compile).
assert batch_callable(ShardedExecutor(data=8), None,
                      gmres_ir_batch_lowerable(IR)) is wrapped
print("PARITY_COMPILE_OK")

# --- service e2e through the sharded path ---------------------------------
import tempfile
from repro.core import TrainConfig, W1
from repro.service import (AutotuneServer, BatcherConfig, OnlineConfig,
                           PolicyRegistry)

def serve(ex, root):
    train = [randsvd_dense(int(n), 50.0, np.random.default_rng(40 + i))
             for i, n in enumerate((10, 12, 14, 11))]
    task = GMRESIRTask(train, SPACE, IR, bucket_step=16, min_bucket=16,
                       executor=ex)
    reg, _, _ = PolicyRegistry.warm_start(root, task, W1,
                                          TrainConfig(episodes=2))
    serve_task = GMRESIRTask((), SPACE, IR, bucket_step=16, min_bucket=16,
                             executor=ex)
    srv = AutotuneServer(reg, serve_task, W1,
                         BatcherConfig(max_batch=4, max_wait_s=0.001,
                                       bucket_step=16, min_bucket=16),
                         OnlineConfig(eps0=0.0, eps_min=0.0), seed=0)
    reqs = [randsvd_dense(int(n), 100.0, np.random.default_rng(100 + i))
            for i, n in enumerate((10, 13, 12, 14, 11, 9))]
    ids = [srv.submit(s) for s in reqs]
    srv.drain()
    return srv, [srv.poll(i) for i in ids]

with tempfile.TemporaryDirectory() as tmp:
    srv_s, resp_s = serve(ShardedExecutor(data=8), tmp + "/s")
    srv_l, resp_l = serve(None, tmp + "/l")
# Flush size tracks mesh width: max_batch 4 -> 8-row flushes.
assert srv_s.batcher.flush_target(16) == 8
assert srv_l.batcher.flush_target(16) == 4
for rs, rl in zip(resp_s, resp_l):
    assert rs.action == rl.action
    assert rs.record.status == rl.record.status
    assert rs.record.metrics == rl.record.metrics
    assert rs.reward == rl.reward
tel = srv_s.telemetry.snapshot()
assert tel["solver_rows"] % 8 == 0
print("PARITY_SERVICE_OK")
"""


def test_sharded_parity_8_devices():
    """Full executor parity suite on a forced 8-device host mesh."""
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    out = subprocess.run([sys.executable, "-c", PARITY_8DEV], env=env,
                         capture_output=True, text=True, timeout=900)
    for marker in ("PARITY_BATCH_OK", "PARITY_ENGINE_OK",
                   "PARITY_COMPILE_OK", "PARITY_SERVICE_OK"):
        assert marker in out.stdout, (marker, out.stdout[-2000:],
                                      out.stderr[-3000:])
