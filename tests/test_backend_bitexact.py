"""Pallas precision backend vs the jnp oracle: bit-exactness + compile
accounting (DESIGN.md §6.2, §6.3).

Both backends run the *same* solver code; only the dispatched ops differ
(`chop` — identical integer RNE elementwise; `chop_mv` — shared
lane-padded row-sum reduction shape). So full GMRES-IR / CG-IR solver
outputs must be bit-identical on a shared f32 carrier, for every format
id, padded or not, single or batched, and end-to-end through the
`AutotuneEngine` and the serving stack. The pallas kernels run in
interpret mode so this suite is CPU-runnable (the CI docs job runs it).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reduced_action_space
from repro.core.engine import AutotuneEngine
from repro.data.matrices import randsvd_dense, sparse_spd
from repro.precision import (FORMAT_ID, FORMAT_LIST, JnpBackend,
                             PallasBackend, resolve_backend)
from repro.service import AutotuneServer, BatcherConfig, OnlineConfig
from repro.solvers import BlockingPolicy, IRConfig, gmres_ir, gmres_ir_batch
from repro.solvers.cg import CGConfig, cg_ir, cg_ir_batch
from repro.tasks import CGIRTask, GMRESIRTask

RNG = np.random.default_rng(123)

# Shared f32 carrier on both sides; small chop_min_elems so the n^2
# roundings inside the solvers actually exercise the pallas chop kernel.
ORACLE = JnpBackend(carrier_dtype="float32")
PALLAS = PallasBackend(interpret=True, chop_min_elems=256)

IR = IRConfig(tau=1e-5, i_max=4, m_max=12)
CG = CGConfig(tau=1e-5, i_max=4, m_max=12)

# Threshold-lowered blocking so the small, cheap test systems exercise
# the blocked LU + blocked trisolve path end to end (DESIGN.md §6.4).
BLOCKED = BlockingPolicy(min_n=16, lu_block=16, trisolve_block=16)
IR_BLK = IRConfig(tau=1e-5, i_max=4, m_max=12, blocking=BLOCKED)
CG_BLK = CGConfig(tau=1e-5, i_max=4, m_max=12, blocking=BLOCKED)

ALL_FMT_IDS = list(range(len(FORMAT_LIST)))

# The `fast` marker names the subset the CI docs job runs (the full
# suite stays in the main tests job) — see [tool.pytest.ini_options].
FAST_FMT_IDS = (FORMAT_ID["fp32"], FORMAT_ID["bf16"])
FMT_PARAMS = [pytest.param(fid, marks=pytest.mark.fast)
              if fid in FAST_FMT_IDS else fid for fid in ALL_FMT_IDS]


def _dense(n, kappa=100.0, seed=0):
    s = randsvd_dense(n, kappa, np.random.default_rng(seed))
    return s.A, s.b, s.x_true


def _spd(n, seed=0):
    s = sparse_spd(n, 0.2, np.random.default_rng(seed), 1e4)
    return s.A, s.b, s.x_true


def _pad(A, b, x, n_pad):
    n = A.shape[0]
    Ap = np.eye(n_pad)
    Ap[:n, :n] = A
    bp = np.zeros(n_pad)
    bp[:n] = b
    xp = np.zeros(n_pad)
    xp[:n] = x
    return Ap, bp, xp


def _assert_stats_equal(got, want):
    for field, g, w in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"field {field}")


# ---------------------------------------------------------------------------
# Solver outputs, all format ids, padded and unpadded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("fid", FMT_PARAMS)
def test_gmres_ir_bitexact(fid, padded):
    A, b, x = _dense(20, kappa=50.0, seed=fid)
    if padded:
        A, b, x = _pad(A, b, x, 32)
    act = jnp.asarray([fid] * 4, jnp.int32)
    got = gmres_ir(A, b, x, act, IR, backend=PALLAS)
    want = gmres_ir(A, b, x, act, IR, backend=ORACLE)
    _assert_stats_equal(got, want)


@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("fid", FMT_PARAMS)
def test_cg_ir_bitexact(fid, padded):
    A, b, x = _spd(20, seed=fid)
    if padded:
        A, b, x = _pad(A, b, x, 32)
    act = jnp.asarray([fid] * 4, jnp.int32)
    got = cg_ir(A, b, x, act, CG, backend=PALLAS)
    want = cg_ir(A, b, x, act, CG, backend=ORACLE)
    _assert_stats_equal(got, want)


# ---------------------------------------------------------------------------
# Factorization path: blocked LU + blocked trisolve (DESIGN.md §6.4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fid", FMT_PARAMS)
def test_gmres_ir_blocked_path_bitexact(fid):
    """Full GMRES-IR through blocked LU (chop_matmul trailing update)
    and blocked trisolves (chop_trisolve preconditioner applications):
    bit-identical across backends for every format id."""
    A, b, x = _dense(20, kappa=50.0, seed=40 + fid)
    act = jnp.asarray([fid] * 4, jnp.int32)
    got = gmres_ir(A, b, x, act, IR_BLK, backend=PALLAS)
    want = gmres_ir(A, b, x, act, IR_BLK, backend=ORACLE)
    _assert_stats_equal(got, want)


@pytest.mark.parametrize("fid", FMT_PARAMS)
def test_cg_ir_blocked_path_bitexact(fid):
    A, b, x = _spd(20, seed=40 + fid)
    act = jnp.asarray([fid] * 4, jnp.int32)
    got = cg_ir(A, b, x, act, CG_BLK, backend=PALLAS)
    want = cg_ir(A, b, x, act, CG_BLK, backend=ORACLE)
    _assert_stats_equal(got, want)


@pytest.mark.fast
def test_blocked_path_batched_bitexact():
    """vmapped blocked path: pallas kernels == oracle, and batched rows
    == single solves."""
    rows = [_dense(20, kappa=10.0 ** k, seed=50 + k) for k in range(1, 4)]
    A = np.stack([r[0] for r in rows])
    b = np.stack([r[1] for r in rows])
    x = np.stack([r[2] for r in rows])
    acts = jnp.asarray([[FORMAT_ID["fp32"]] * 4,
                        [FORMAT_ID["bf16"]] * 4,
                        [FORMAT_ID["fp16"], FORMAT_ID["fp32"],
                         FORMAT_ID["fp32"], FORMAT_ID["fp32"]]], jnp.int32)
    got = gmres_ir_batch(A, b, x, acts, IR_BLK, backend=PALLAS)
    want = gmres_ir_batch(A, b, x, acts, IR_BLK, backend=ORACLE)
    _assert_stats_equal(got, want)
    for i in range(3):
        single = gmres_ir(A[i], b[i], x[i], acts[i], IR_BLK, backend=PALLAS)
        for field, g, w in zip(single._fields, single, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w)[i],
                                          err_msg=f"row {i} field {field}")


def test_blocked_path_default_threshold_bitexact():
    """At n >= DEFAULT_BLOCKING.min_n the blocked path engages by
    default; the whole factorization + substitution pipeline must stay
    bit-identical across backends at that production size (the
    acceptance gate for making blocked the default).

    Scoped to the LU + trisolve pipeline rather than full GMRES-IR:
    whole-solver outputs at this size are limited by a pre-existing
    sensitivity of *unrounded* carrier reductions (GMRES norms) to each
    program's fusion context, which affects the strict path identically
    and is independent of the blocked subsystem (the small-n suites
    above cover full-solver bit-equality for both paths)."""
    from repro.solvers import DEFAULT_BLOCKING, lu_factor_auto, lu_solve
    n = DEFAULT_BLOCKING.min_n
    A, b, _ = _dense(n, kappa=100.0, seed=4)
    for fid in (FORMAT_ID["bf16"], FORMAT_ID["fp32"]):
        fj = lu_factor_auto(ORACLE.coerce(jnp.asarray(A)), fid,
                            backend=ORACLE, blocking=DEFAULT_BLOCKING)
        fp = lu_factor_auto(PALLAS.coerce(jnp.asarray(A)), fid,
                            backend=PALLAS, blocking=DEFAULT_BLOCKING)
        np.testing.assert_array_equal(np.asarray(fj.lu),
                                      np.asarray(fp.lu),
                                      err_msg=f"fmt {fid}")
        np.testing.assert_array_equal(np.asarray(fj.perm),
                                      np.asarray(fp.perm))
        xj = lu_solve(fj.lu, fj.perm, ORACLE.coerce(jnp.asarray(b)), fid,
                      backend=ORACLE, blocking=DEFAULT_BLOCKING)
        xp = lu_solve(fp.lu, fp.perm, PALLAS.coerce(jnp.asarray(b)), fid,
                      backend=PALLAS, blocking=DEFAULT_BLOCKING)
        np.testing.assert_array_equal(np.asarray(xj), np.asarray(xp),
                                      err_msg=f"fmt {fid}")


@pytest.mark.fast
def test_mixed_action_bitexact():
    """Per-step format ids differing across the four roles."""
    A, b, x = _dense(20, kappa=1e3, seed=99)
    act = jnp.asarray([FORMAT_ID["bf16"], FORMAT_ID["fp32"],
                       FORMAT_ID["fp16"], FORMAT_ID["fp32"]], jnp.int32)
    _assert_stats_equal(gmres_ir(A, b, x, act, IR, backend=PALLAS),
                        gmres_ir(A, b, x, act, IR, backend=ORACLE))


@pytest.mark.fast
def test_batched_bitexact_and_matches_single():
    """vmapped pallas kernels == vmapped oracle == per-row solves."""
    rows = [_dense(20, kappa=10.0 ** k, seed=k) for k in range(1, 4)]
    A = np.stack([r[0] for r in rows])
    b = np.stack([r[1] for r in rows])
    x = np.stack([r[2] for r in rows])
    acts = jnp.asarray([[FORMAT_ID["fp32"]] * 4,
                        [FORMAT_ID["bf16"]] * 4,
                        [FORMAT_ID["fp16"], FORMAT_ID["fp32"],
                         FORMAT_ID["fp32"], FORMAT_ID["fp32"]]], jnp.int32)
    got = gmres_ir_batch(A, b, x, acts, IR, backend=PALLAS)
    want = gmres_ir_batch(A, b, x, acts, IR, backend=ORACLE)
    _assert_stats_equal(got, want)
    for i in range(3):
        single = gmres_ir(A[i], b[i], x[i], acts[i], IR, backend=PALLAS)
        for field, g, w in zip(single._fields, single, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w)[i],
                                          err_msg=f"row {i} field {field}")


# ---------------------------------------------------------------------------
# Zero recompiles across precision actions (one executable per bucket)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [ORACLE, PALLAS],
                         ids=["jnp", "pallas-interpret"])
def test_zero_recompiles_across_actions(backend):
    """Sweeping every action of the space through the engine must reuse
    ONE batched executable per size bucket (DESIGN.md §3.4, §6.3). The
    engine dispatches through the per-shape AOT executable cache
    (DESIGN.md §12), so the invariant is asserted there."""
    from repro.core.executor import batch_callable
    from repro.solvers import gmres_ir_batch_lowerable
    rng = np.random.default_rng(5)
    space = reduced_action_space()
    systems = [randsvd_dense(int(n), 100.0, rng) for n in (10, 12, 14)]
    task = GMRESIRTask(systems, space, IR, bucket_step=16, min_bucket=16,
                       backend=backend)
    engine = AutotuneEngine(task, chunk=4)
    wrapped = batch_callable(task.executor, None,
                             gmres_ir_batch_lowerable(IR, backend))
    before = len(wrapped.executables)
    engine.prefill_all()                     # every (instance, action) pair
    assert engine.n_solves == 3 * space.n_actions
    # One bucket (all n pad to 16) -> exactly one new executable.
    assert len(wrapped.executables) - before == 1


def test_zero_recompiles_cg_across_actions():
    from repro.core.executor import batch_callable
    from repro.solvers import cg_ir_batch_lowerable
    rng = np.random.default_rng(6)
    space = reduced_action_space()
    systems = [sparse_spd(int(n), 0.2, rng, 1e4) for n in (10, 12, 14)]
    task = CGIRTask(systems, space, CG, bucket_step=16, min_bucket=16,
                    backend=PALLAS)
    engine = AutotuneEngine(task, chunk=4)
    wrapped = batch_callable(task.executor, None,
                             cg_ir_batch_lowerable(CG, PALLAS))
    before = len(wrapped.executables)
    engine.prefill_all()
    assert len(wrapped.executables) - before == 1


# ---------------------------------------------------------------------------
# End to end: AutotuneEngine and the serving stack
# ---------------------------------------------------------------------------

def _engine_outcomes(task_cls, systems, cfg, backend):
    space = reduced_action_space()
    kw = ({"ir_cfg": cfg} if task_cls is GMRESIRTask else {"cg_cfg": cfg})
    task = task_cls(systems, space, bucket_step=16, min_bucket=16,
                    backend=backend, **kw)
    engine = AutotuneEngine(task, chunk=4)
    engine.prefill_all()
    return engine, space


@pytest.mark.parametrize("task_cls,gen,cfg", [
    (GMRESIRTask, _dense, IR), (CGIRTask, _spd, CG)],
    ids=["gmres_ir", "cg_ir"])
def test_engine_outcomes_bitexact(task_cls, gen, cfg):
    """The full engine path (bucketing, identity padding, fixed-chunk
    stacking, batched solve) produces bit-identical Outcomes on both
    backends for every (instance, action) pair."""
    rng = np.random.default_rng(7)
    if task_cls is GMRESIRTask:
        systems = [randsvd_dense(int(n), 100.0, rng) for n in (9, 11, 13)]
    else:
        systems = [sparse_spd(int(n), 0.2, rng, 1e4) for n in (9, 11, 13)]
    eng_p, space = _engine_outcomes(task_cls, systems, cfg, PALLAS)
    eng_j, _ = _engine_outcomes(task_cls, systems, cfg, ORACLE)
    for i in range(len(systems)):
        for a in range(space.n_actions):
            got = eng_p.outcome(i, a)
            want = eng_j.outcome(i, a)
            assert got.status == want.status, (i, a)
            assert got.metrics == want.metrics, (i, a)


def test_serving_stack_bitexact(tmp_path):
    """Same stream of requests through two AutotuneServers (pallas vs jnp
    oracle) with exploration off: identical actions, bit-identical
    Outcomes, identical rewards."""
    rng = np.random.default_rng(8)
    space = reduced_action_space()
    from repro.core import TrainConfig, W1
    from repro.service import PolicyRegistry

    train = [randsvd_dense(int(n), 50.0, rng) for n in (10, 12, 14, 11)]
    bcfg = BatcherConfig(max_batch=4, max_wait_s=0.001,
                         bucket_step=16, min_bucket=16)
    ocfg = OnlineConfig(eps0=0.0, eps_min=0.0)

    def run(backend, sub):
        task = GMRESIRTask(train, space, IR, bucket_step=16, min_bucket=16,
                           backend=backend)
        reg, _, _ = PolicyRegistry.warm_start(
            str(tmp_path / sub), task, W1, TrainConfig(episodes=2))
        serve_task = GMRESIRTask((), space, IR, bucket_step=16,
                                 min_bucket=16, backend=backend)
        srv = AutotuneServer(reg, serve_task, W1, bcfg, ocfg, seed=0)
        reqs = [randsvd_dense(int(n), 100.0, np.random.default_rng(100 + i))
                for i, n in enumerate((10, 13, 12, 14, 11, 9))]
        ids = [srv.submit(s) for s in reqs]
        srv.drain()
        return [srv.poll(i) for i in ids]

    resp_p = run(PALLAS, "p")
    resp_j = run(ORACLE, "j")
    for rp, rj in zip(resp_p, resp_j):
        assert rp.action == rj.action
        assert rp.record.status == rj.record.status
        assert rp.record.metrics == rj.record.metrics
        assert rp.reward == rj.reward


# ---------------------------------------------------------------------------
# Backend selection mechanics
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_pallas_falls_back_to_jnp_off_tpu():
    """There is no fallback: off a TPU, naming the compiled 'pallas'
    backend raises and says how to get the interpreter, and a process
    that names no backend resolves to 'jnp'."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("on TPU the pallas backend is served compiled")
    with pytest.raises(RuntimeError, match="pallas-interpret"):
        resolve_backend("pallas")
    assert resolve_backend("pallas-interpret").name == "pallas"
    assert resolve_backend("pallas-interpret").interpret
    assert resolve_backend(None).name == "jnp"


@pytest.mark.fast
def test_tpu_process_defaults_to_compiled_pallas(monkeypatch):
    """Where no backend is named, the platform decides: a TPU process
    serves the compiled pallas kernels (never the interpreter)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bk = resolve_backend(None)
    assert isinstance(bk, PallasBackend) and not bk.interpret
    assert resolve_backend("pallas") == bk


@pytest.mark.fast
def test_f64_carrier_refused_on_tpu(monkeypatch):
    """A TPU cannot bitcast f64 to u64: rounding on an f64 carrier is
    refused while tracing, with the reason, before any compile; the f32
    carrier is untouched."""
    import jax

    from repro.precision import chop
    x64, x32 = jnp.ones(4, jnp.float64), jnp.ones(4, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(TypeError, match="f64 carrier does not run on a TPU"):
        chop(x64, FORMAT_ID["bf16"])
    with pytest.raises(TypeError, match="f64 carrier does not run on a TPU"):
        jax.jit(lambda v: chop(v, FORMAT_ID["fp32"])).lower(x64)
    np.testing.assert_array_equal(np.asarray(chop(x32, FORMAT_ID["bf16"])),
                                  np.ones(4, np.float32))


@pytest.mark.fast
def test_env_var_selects_default(monkeypatch):
    """No environment variable selects the precision backend: where none
    is named, the platform does (a CPU here, so "jnp"); a name or an
    instance still resolves as given."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv("REPRO_SOLVE_EXECUTOR", "sharded")
    assert resolve_backend(None) == JnpBackend()
    assert resolve_backend("pallas-interpret") == PallasBackend(
        interpret=True)
    assert resolve_backend("jnp") == JnpBackend()
    inst = PallasBackend(interpret=True, chop_min_elems=1)
    assert resolve_backend(inst) is inst
    with pytest.raises(KeyError, match="nope"):
        resolve_backend("nope")


@pytest.mark.fast
def test_backends_hash_by_value():
    """Equal-valued backends must share one jit executable."""
    assert hash(PallasBackend(interpret=True)) == hash(
        PallasBackend(interpret=True))
    assert PallasBackend(interpret=True) == PallasBackend(interpret=True)
    assert JnpBackend() == JnpBackend()
    assert JnpBackend() != JnpBackend(carrier_dtype="float32")
