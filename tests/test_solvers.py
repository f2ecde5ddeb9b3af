"""Solver substrate tests: LU, triangular, GMRES, GMRES-IR, and the
refinement loop GMRES-IR and CG-IR share."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.linalg as sla
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.precision import FORMAT_ID, FORMATS, DoubleWord, JnpBackend, dw
from repro.solvers import (CONVERGED, FAILED, MAXITER, CGConfig, IRConfig,
                           STAGNATED, cg, gmres_ir, gmres_ir_batch,
                           gmres_precond, ir, lu_factor, lu_factor_blocked,
                           lu_solve, solve_unit_lower, solve_upper)

RNG = np.random.default_rng(42)
FP64 = FORMAT_ID["fp64"]
FP32 = FORMAT_ID["fp32"]
BF16 = FORMAT_ID["bf16"]
TF32 = FORMAT_ID["tf32"]


def rand_system(n, kappa=None, rng=RNG):
    if kappa is None:
        A = rng.standard_normal((n, n))
    else:
        q1, _ = np.linalg.qr(rng.standard_normal((n, n)))
        q2, _ = np.linalg.qr(rng.standard_normal((n, n)))
        s = np.ones(n)
        s[-1] = 1.0 / kappa
        A = (q1 * s) @ q2.T
    x = rng.standard_normal(n)
    return A, A @ x, x


# ---------------------------------------------------------------------------
# LU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [4, 17, 64])
def test_lu_fp64_matches_numpy(n):
    A, b, x = rand_system(n)
    lu = lu_factor(jnp.asarray(A), FP64)
    assert not bool(lu.fail)
    # P A = L U
    L = np.tril(np.asarray(lu.lu), -1) + np.eye(n)
    U = np.triu(np.asarray(lu.lu))
    PA = A[np.asarray(lu.perm)]
    np.testing.assert_allclose(L @ U, PA, atol=1e-10 * np.abs(A).max() * n)
    got = np.asarray(lu_solve(lu.lu, lu.perm, jnp.asarray(b), FP64))
    np.testing.assert_allclose(got, np.linalg.solve(A, b), rtol=0, atol=1e-9)


def test_lu_partial_pivoting_stability():
    """Matrix requiring pivoting (tiny leading pivot)."""
    A = np.array([[1e-20, 1.0], [1.0, 1.0]])
    lu = lu_factor(jnp.asarray(A), FP64)
    b = np.array([1.0, 2.0])
    got = np.asarray(lu_solve(lu.lu, lu.perm, jnp.asarray(b), FP64))
    np.testing.assert_allclose(got, np.linalg.solve(A, b), rtol=1e-12)


def test_lu_low_precision_error_scales_with_u():
    A, b, x = rand_system(48, kappa=10)
    errs = {}
    for name in ["bf16", "fp32", "fp64"]:
        lu = lu_factor(jnp.asarray(A), FORMAT_ID[name])
        got = np.asarray(lu_solve(lu.lu, lu.perm, jnp.asarray(b),
                                  FORMAT_ID[name]))
        errs[name] = np.max(np.abs(got - x)) / np.max(np.abs(x))
    assert errs["bf16"] > errs["fp32"] > errs["fp64"]
    assert errs["bf16"] < 48 * 10 * FORMATS["bf16"].unit_roundoff * 10


def test_lu_overflow_sets_fail():
    """fp16 overflows on entries beyond 65504."""
    A = np.diag(np.full(8, 1e6))
    lu = lu_factor(jnp.asarray(A), FORMAT_ID["fp16"])
    assert bool(lu.fail)


def test_lu_blocked_matches_strict_fp64():
    A, _, _ = rand_system(64)
    s = lu_factor(jnp.asarray(A), FP64)
    blk = lu_factor_blocked(jnp.asarray(A), FP64, block=16)
    xs = np.asarray(lu_solve(s.lu, s.perm, jnp.ones(64), FP64))
    xb = np.asarray(lu_solve(blk.lu, blk.perm, jnp.ones(64), FP64))
    np.testing.assert_allclose(xs, xb, rtol=1e-8, atol=1e-10)


# ---------------------------------------------------------------------------
# Triangular solves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3, 32])
def test_triangular_fp64_exactish(n):
    Lfull = np.tril(RNG.standard_normal((n, n)), -1)
    U = np.triu(RNG.standard_normal((n, n))) + np.eye(n) * n
    b = RNG.standard_normal(n)
    comb = Lfull + U
    y = np.asarray(solve_unit_lower(jnp.asarray(comb), jnp.asarray(b), FP64))
    np.testing.assert_allclose(y, sla.solve_triangular(Lfull + np.eye(n), b,
                                                       lower=True), rtol=1e-10)
    x = np.asarray(solve_upper(jnp.asarray(comb), jnp.asarray(b), FP64))
    np.testing.assert_allclose(x, sla.solve_triangular(U, b), rtol=1e-8)


# ---------------------------------------------------------------------------
# GMRES
# ---------------------------------------------------------------------------

def test_gmres_solves_preconditioned_system():
    n = 48
    A, b, x = rand_system(n, kappa=100)
    lu = lu_factor(jnp.asarray(A), FP64)
    res = gmres_precond(jnp.asarray(A), lu.lu, lu.perm, jnp.asarray(b),
                        FP64, m_max=30, tol=1e-12)
    assert not bool(res.fail)
    np.testing.assert_allclose(np.asarray(res.z), x, rtol=0, atol=1e-8)
    assert int(res.iters) <= 3  # exact preconditioner => ~1 iteration


def test_gmres_low_precision_needs_more_iterations():
    n = 48
    A, b, x = rand_system(n, kappa=1000)
    lo = lu_factor(jnp.asarray(A), BF16)
    hi = lu_factor(jnp.asarray(A), FP64)
    r_lo = gmres_precond(jnp.asarray(A), lo.lu, lo.perm, jnp.asarray(b),
                         FP64, m_max=40, tol=1e-10)
    r_hi = gmres_precond(jnp.asarray(A), hi.lu, hi.perm, jnp.asarray(b),
                         FP64, m_max=40, tol=1e-10)
    assert int(r_lo.iters) > int(r_hi.iters)


# ---------------------------------------------------------------------------
# GMRES-IR end to end
# ---------------------------------------------------------------------------

def test_ir_fp64_baseline_two_iterations():
    """The paper's FP64 baseline accounting: exactly 2 outer iterations."""
    for kappa in [10, 1e5, 1e8]:
        A, b, x = rand_system(96, kappa=kappa)
        st_ = gmres_ir(jnp.asarray(A), jnp.asarray(b), jnp.asarray(x),
                       jnp.asarray([FP64] * 4, jnp.int32), IRConfig(tau=1e-6))
        assert int(st_.status) == CONVERGED
        assert int(st_.n_outer) == 2
        assert float(st_.nbe) < 1e-15


def test_ir_low_precision_factorization_converges_wellconditioned():
    A, b, x = rand_system(96, kappa=50)
    act = jnp.asarray([BF16, FP64, FP64, FP64], jnp.int32)
    st_ = gmres_ir(jnp.asarray(A), jnp.asarray(b), jnp.asarray(x), act,
                   IRConfig(tau=1e-6))
    assert int(st_.status) == CONVERGED
    assert float(st_.ferr) < 1e-10
    assert int(st_.n_gmres) > 2  # pays extra inner iterations


def test_ir_all_low_precision_degrades():
    A, b, x = rand_system(96, kappa=1e4)
    act = jnp.asarray([BF16, BF16, BF16, BF16], jnp.int32)
    st_ = gmres_ir(jnp.asarray(A), jnp.asarray(b), jnp.asarray(x), act,
                   IRConfig(tau=1e-6))
    assert float(st_.ferr) > 1e-6  # cannot reach fp64-level accuracy


def test_ir_singular_matrix_fails():
    A = np.zeros((16, 16))
    st_ = gmres_ir(jnp.asarray(A), jnp.ones(16), jnp.ones(16),
                   jnp.asarray([FP64] * 4, jnp.int32), IRConfig())
    assert int(st_.status) == FAILED


def test_ir_batch_matches_single():
    systems = [rand_system(48, kappa=k) for k in [10, 1e4, 1e7]]
    A = jnp.asarray(np.stack([s[0] for s in systems]))
    b = jnp.asarray(np.stack([s[1] for s in systems]))
    x = jnp.asarray(np.stack([s[2] for s in systems]))
    acts = jnp.asarray(np.stack([[FP64] * 4, [FP32, FP64, FP64, FP64],
                                 [BF16, FP32, FP64, FP64]]), jnp.int32)
    cfg = IRConfig(tau=1e-6)
    batch = gmres_ir_batch(A, b, x, acts, cfg)
    for i in range(3):
        single = gmres_ir(A[i], b[i], x[i], acts[i], cfg)
        assert int(batch.status[i]) == int(single.status)
        assert int(batch.n_outer[i]) == int(single.n_outer)
        np.testing.assert_allclose(float(batch.ferr[i]), float(single.ferr),
                                   rtol=1e-12)


def test_ir_padded_system_equivalent():
    """Identity-padding must not change the solution quality (DESIGN §3)."""
    A, b, x = rand_system(48, kappa=1e3)
    n_pad = 64
    Ap = np.eye(n_pad)
    Ap[:48, :48] = A
    bp = np.zeros(n_pad)
    bp[:48] = b
    xp = np.zeros(n_pad)
    xp[:48] = x
    act = jnp.asarray([FP32, FP64, FP64, FP64], jnp.int32)
    st0 = gmres_ir(jnp.asarray(A), jnp.asarray(b), jnp.asarray(x), act,
                   IRConfig(tau=1e-6))
    st1 = gmres_ir(jnp.asarray(Ap), jnp.asarray(bp), jnp.asarray(xp), act,
                   IRConfig(tau=1e-6))
    assert int(st1.status) == CONVERGED
    assert abs(np.log10(float(st0.ferr)) - np.log10(float(st1.ferr))) < 2.0


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=8, max_value=40),
       st.sampled_from([1e1, 1e3, 1e6]))
def test_prop_monotone_precision_error(n, kappa):
    """Error is (weakly) monotone in factorization precision."""
    rng = np.random.default_rng(n * 1000 + int(np.log10(kappa)))
    A, b, x = rand_system(n, kappa=kappa, rng=rng)
    cfg = IRConfig(tau=1e-8, i_max=6)
    errs = []
    for fid in [BF16, FP32, FP64]:
        act = jnp.asarray([fid, FP64, FP64, FP64], jnp.int32)
        st_ = gmres_ir(jnp.asarray(A), jnp.asarray(b), jnp.asarray(x), act,
                       cfg)
        errs.append(float(st_.ferr))
    # Converged IR reaches the same error floor regardless of u_f, but
    # non-converged low-precision runs must not be better than fp64.
    assert errs[0] >= errs[2] * 0.01
    assert errs[1] >= errs[2] * 0.01


# ---------------------------------------------------------------------------
# The one refinement loop (Alg. 2, Eqs. 14-16) under each step
# ---------------------------------------------------------------------------

# Each solver's batch entry point with its carrier, called unjitted so
# the spy below is traced into this call's program and no other.
LOOP_SOLVERS = {
    "gmres-jnp": (ir._gmres_ir_batch_jit, ir._prepare, JnpBackend(),
                  IRConfig(tau=1e-10, i_max=6, m_max=24)),
    "gmres-dw": (ir._gmres_ir_batch_jit, ir._prepare,
                 DoubleWord(JnpBackend()),
                 IRConfig(tau=1e-10, i_max=6, m_max=24)),
    "cg-jnp": (cg._cg_ir_batch_jit, cg._prepare, JnpBackend(),
               CGConfig(tau=1e-10, i_max=6, m_max=30)),
}
# (arm, kappa, zero column, poison the second step's x_new with NaN,
# the statuses the case may end in)
LOOP_CASES = {
    "converged": ([FP64] * 4, 1e2, False, False, {CONVERGED}),
    "stagnated_or_maxiter": ([BF16, FP64, TF32, FP64], 1e6, False, False,
                             {STAGNATED, MAXITER}),
    "lu_fails": ([FP64] * 4, 1e2, True, False, {FAILED}),
    "step_fails": ([FP64] * 4, 1e2, False, True, {FAILED}),
}


def _loop_system(solver, kappa, zero_col, n=24):
    rng = np.random.default_rng(int(np.log10(kappa)) + 7 * zero_col)
    if solver.startswith("cg"):
        q, _ = np.linalg.qr(rng.standard_normal((n, n)))
        A = (q * np.logspace(0, -np.log10(kappa), n)) @ q.T
        A, x = (A + A.T) / 2, rng.standard_normal(n)
    else:
        A, _, x = rand_system(n, kappa=kappa, rng=rng)
    if zero_col:
        A[:, n // 2] = 0.0
    return A, A @ x, x


def _flat(tree):
    return [np.asarray(v).reshape(-1) for v in jax.tree_util.tree_leaves(
        tree)]


def _scalar(v):
    return np.asarray(v).reshape(-1)[0]


@pytest.mark.parametrize("case", sorted(LOOP_CASES))
@pytest.mark.parametrize("solver", sorted(LOOP_SOLVERS))
def test_refinement_loop_follows_eqs_14_16(solver, case, monkeypatch):
    """GMRES-IR on both carriers and CG-IR run one outer loop
    (`ir.refine`): replayed from the steps it took, its status and
    n_outer follow Eqs. 14-16 with the cascade FAILED > CONVERGED >
    STAGNATED > MAXITER, an LU failure skips the loop, and a failed step
    (here x_new poisoned to NaN) keeps x."""
    entry, prepare, bk, cfg = LOOP_SOLVERS[solver]
    arm, kappa, zero_col, poison, ends_in = LOOP_CASES[case]
    steps, ends = [], []
    refine = ir.refine

    def spy(step, x0, lu_fail, conv_tol, cfg_):
        def logged(x):
            z, x_new, iters, fail = step(x)
            if poison:
                x_new = jax.tree_util.tree_map(lambda v: jnp.where(
                    jnp.any(ir._lead(x) != 0), jnp.nan, v), x_new)
            jax.debug.callback(lambda *a: steps.append(a), x, z, x_new,
                               fail, conv_tol)
            return z, x_new, iters, fail

        out = refine(logged, x0, lu_fail, conv_tol, cfg_)
        jax.debug.callback(lambda *a: ends.append(a), x0, *out)
        return out

    monkeypatch.setattr(ir, "refine", spy)
    A, b, x = _loop_system(solver, kappa, zero_col)
    st_ = entry.__wrapped__(*prepare(bk, A[None], b[None], x[None],
                                     np.asarray([arm], np.int32)), cfg, bk)
    (x0, x_end, n_outer, _, status), = ends
    status = int(_scalar(status))
    assert int(st_.status[0]) == status
    assert int(st_.n_outer[0]) == int(_scalar(n_outer)) == len(steps)

    def lead(v):
        return _flat(v)[0]

    want, znorm_prev, x_kept = MAXITER, np.inf, x0
    for i, (x_in, z, x_new, fail, conv_tol) in enumerate(steps):
        for got, kept in zip(_flat(x_in), _flat(x_kept)):
            np.testing.assert_array_equal(got, kept)
        znorm = np.max(np.abs(lead(z)))
        converged = znorm <= _scalar(conv_tol) * np.max(np.abs(lead(x_new)))
        stagnated = i > 0 and znorm >= cfg.stag_tol * znorm_prev
        hit_max = i + 1 >= cfg.i_max
        failed = bool(_scalar(fail)) or not np.all(np.isfinite(lead(x_new)))
        want = (FAILED if failed else CONVERGED if converged
                else STAGNATED if stagnated else MAXITER if hit_max
                else want)
        done = converged or stagnated or hit_max or failed
        assert done == (i == len(steps) - 1)
        x_kept = x_in if failed else x_new
        znorm_prev = znorm
    for got, kept in zip(_flat(x_end), _flat(x_kept)):
        np.testing.assert_array_equal(got, kept)
    if zero_col:
        assert steps == [] and not np.any(_flat(x_end)[0])
    else:
        assert steps and want == status
    if poison:
        assert len(steps) == 2
    assert status in ends_in
