"""Blocked factorization/substitution hot path (DESIGN.md §6.2, §6.4).

Covers the blocked-LU + blocked-trisolve subsystem:
  * property: blocked panel-pivoted factors solve the same systems as
    the strict factors (residual-level agreement across all format ids);
  * bit-exactness of the trisolve kernel vs its jnp oracle — padded and
    unpadded, single and batched (every system of a vmapped call in one
    launch, one format per row), lower and upper — and the gauge of
    systems per launch;
  * bit-exactness of the pinned-contract chopped GEMM
    (`backend.chop_matmul`) across backends, padded and batched;
  * the internal identity padding of `lu_factor_blocked` at sizes that
    are not a block multiple (the old `assert n % block == 0` is gone);
  * the documented double-rounding division semantics of `solve_upper`
    (`chop(chop(y - s) / safe)`), pinned so backends cannot drift;
  * size-threshold dispatch: `lu_factor_auto` / triangular solves take
    the blocked path at `blocking.min_n` and the strict path below.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.qmatmul import qgemm_op, qgemm_ref
from repro.kernels.trisolve import trisolve_op, trisolve_ref
from repro.precision import (FORMAT_ID, FORMAT_LIST, JnpBackend,
                             PallasBackend)
from repro.precision.chop import chop
from repro.solvers import (BlockingPolicy, STRICT_ONLY, lu_factor,
                           lu_factor_auto, lu_factor_blocked, lu_solve,
                           solve_unit_lower, solve_upper)

RNG = np.random.default_rng(77)
FP64 = FORMAT_ID["fp64"]
FP32 = FORMAT_ID["fp32"]
BF16 = FORMAT_ID["bf16"]

ORACLE = JnpBackend(carrier_dtype="float32")
PALLAS = PallasBackend(interpret=True, chop_min_elems=256)

ALL_FMT_IDS = list(range(len(FORMAT_LIST)))


def rand_system(n, kappa=100.0, rng=RNG):
    q1, _ = np.linalg.qr(rng.standard_normal((n, n)))
    q2, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.ones(n)
    s[-1] = 1.0 / kappa
    A = (q1 * s) @ q2.T
    x = rng.standard_normal(n)
    return A, A @ x, x


def tri_factors(n, rng=RNG, scale=4.0):
    """A combined-LU-layout matrix with a well-conditioned triangle."""
    M = rng.standard_normal((n, n))
    M[np.arange(n), np.arange(n)] = scale + rng.uniform(1, 2, n)
    return M


# ---------------------------------------------------------------------------
# Blocked LU: padding, correctness, strict agreement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block", [(40, 16), (100, 64), (96, 32)])
def test_blocked_lu_pads_non_multiple_sizes(n, block):
    """Regression for the old `assert n % block == 0`: every size takes
    the blocked path via internal identity padding."""
    A, b, x = rand_system(n, kappa=10.0)
    f = lu_factor_blocked(jnp.asarray(A), FP64, block=block)
    assert not bool(f.fail)
    assert f.lu.shape == (n, n) and f.perm.shape == (n,)
    got = np.asarray(lu_solve(f.lu, f.perm, jnp.asarray(b), FP64))
    np.testing.assert_allclose(got, np.linalg.solve(A, b),
                               rtol=0, atol=1e-9)


@pytest.mark.parametrize("fid", ALL_FMT_IDS)
def test_blocked_factors_solve_same_systems(fid):
    """Property: blocked panel-pivoted factors are as good a solver as
    the strict factors, for every format id (residual-level agreement;
    the factorizations themselves legitimately differ bitwise)."""
    A, b, x = rand_system(48, kappa=30.0,
                          rng=np.random.default_rng(100 + fid))
    fs = lu_factor(jnp.asarray(A), fid)
    fb = lu_factor_blocked(jnp.asarray(A), fid, block=16)
    assert bool(fs.fail) == bool(fb.fail)
    if bool(fs.fail):       # fp8 overflow etc.: both paths must agree
        return
    norm = np.abs(A).sum(axis=1).max()

    def resid(f):
        sol = np.asarray(lu_solve(f.lu, f.perm, jnp.asarray(b), fid))
        if not np.all(np.isfinite(sol)):
            return np.inf
        return np.max(np.abs(b - A @ sol)) / (
            norm * np.max(np.abs(sol)) + np.max(np.abs(b)))

    rs, rb = resid(fs), resid(fb)
    # Same error floor up to a modest constant (both are backward-stable
    # eliminations at the same precision).
    assert np.isfinite(rb)
    assert rb <= 50 * rs + 1e-14, (rs, rb)


@pytest.mark.parametrize("n", [17, 64])
def test_lu_factor_auto_dispatch(n):
    """Below min_n: bitwise the strict factorization; above: the blocked
    one. The dispatch is by static shape only."""
    A, _, _ = rand_system(n, kappa=10.0)
    pol = BlockingPolicy(min_n=32, lu_block=16)
    auto = lu_factor_auto(jnp.asarray(A), FP32, blocking=pol)
    if n < 32:
        want = lu_factor(jnp.asarray(A), FP32)
    else:
        want = lu_factor_blocked(jnp.asarray(A), FP32, block=16)
    np.testing.assert_array_equal(np.asarray(auto.lu), np.asarray(want.lu))
    np.testing.assert_array_equal(np.asarray(auto.perm),
                                  np.asarray(want.perm))


def test_blocked_lu_bitexact_across_backends():
    """Shared trace + bit-exact dispatched ops (chop, pinned-contract
    chop_matmul) => identical factor bits on jnp and pallas-interpret."""
    for fid in (FP32, BF16, FORMAT_ID["fp16"]):
        A, _, _ = rand_system(48, kappa=20.0,
                              rng=np.random.default_rng(fid))
        fj = lu_factor_blocked(ORACLE.coerce(jnp.asarray(A)), fid,
                               block=16, backend=ORACLE)
        fp = lu_factor_blocked(PALLAS.coerce(jnp.asarray(A)), fid,
                               block=16, backend=PALLAS)
        np.testing.assert_array_equal(np.asarray(fj.lu), np.asarray(fp.lu),
                                      err_msg=f"fmt {fid}")
        np.testing.assert_array_equal(np.asarray(fj.perm),
                                      np.asarray(fp.perm))
        assert bool(fj.fail) == bool(fp.fail)


# ---------------------------------------------------------------------------
# Trisolve kernel vs jnp oracle: bit-exactness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lower", [True, False], ids=["lower", "upper"])
@pytest.mark.parametrize("n,block", [(64, 16), (40, 16), (50, 32)],
                         ids=["unpadded", "padded", "padded-wide"])
@pytest.mark.parametrize("fid", [FP32, BF16, FORMAT_ID["e4m3"]])
def test_trisolve_kernel_matches_oracle(fid, n, block, lower):
    rng = np.random.default_rng(10 * n + fid)
    Lu = jnp.asarray(tri_factors(n, rng), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = trisolve_op(Lu, b, fid, lower=lower, block=block, interpret=True)
    want = trisolve_ref(Lu, b, fid, lower=lower, block=block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lower", [True, False], ids=["lower", "upper"])
@pytest.mark.parametrize("n,block", [(48, 16), (40, 16), (256, 128),
                                     (200, 128)],
                         ids=["unpadded", "padded", "unpadded-lane",
                              "padded-lane"])
@pytest.mark.parametrize("batch", [1, 3, 8, 11])
def test_trisolve_kernel_matches_oracle_batched(batch, n, block, lower):
    """The vmapped kernel runs every system of the batch in one launch,
    one per sublane (grid groups of at most 8): each row must equal the
    oracle and the same system solved alone, bit for bit, with a
    different format per row (all seven ids across the cases)."""
    rng = np.random.default_rng(1000 * batch + n)
    Lus = jnp.asarray(np.stack([tri_factors(n, rng) for _ in range(batch)]),
                      jnp.float32)
    bs = jnp.asarray(rng.standard_normal((batch, n)), jnp.float32)
    fids = jnp.asarray((np.arange(batch) + batch + n) % len(FORMAT_LIST),
                       jnp.int32)
    got = jax.vmap(lambda L, b, f: trisolve_op(
        L, b, f, lower=lower, block=block, interpret=True))(Lus, bs, fids)
    want = jax.vmap(lambda L, b, f: trisolve_ref(
        L, b, f, lower=lower, block=block))(Lus, bs, fids)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    # ... and batched == single (row-independent solves).
    for i in range(batch):
        single = trisolve_op(Lus[i], bs[i], fids[i], lower=lower,
                             block=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(single).view(np.uint32),
                                      np.asarray(got)[i].view(np.uint32))


def test_trisolve_systems_per_launch_gauge():
    """`repro_trisolve_systems_per_launch{bucket}` reads the systems one
    traced launch solves: a flush's 8 rows for a vmapped bucket-256
    solve (the blocked path's lower and upper launches), 1 unbatched."""
    from repro.obs.metrics import default_registry
    n = 256
    LU = jnp.asarray(tri_factors(n), jnp.float32)
    perm = jnp.arange(n)
    b = jnp.ones((n,), jnp.float32)

    def solve(L, p, v, fid):
        return lu_solve(L, p, v, fid, backend=PALLAS)

    def systems():
        fam = default_registry().gauge("repro_trisolve_systems_per_launch",
                                       "", ("bucket",))
        return fam.labels(bucket=n).value

    jax.make_jaxpr(jax.vmap(solve))(jnp.stack([LU] * 8),
                                    jnp.stack([perm] * 8),
                                    jnp.stack([b] * 8),
                                    jnp.arange(8, dtype=jnp.int32) % 7)
    assert systems() == 8
    jax.make_jaxpr(solve)(LU, perm, b, jnp.int32(BF16))
    assert systems() == 1


def test_trisolve_matches_strict_solution_fp64():
    """Blocked substitution solves the same triangular systems as the
    strict row loop (residual-level; roundings differ by design)."""
    import scipy.linalg as sla
    rng = np.random.default_rng(3)
    n = 96
    Lu = tri_factors(n, rng, scale=8.0)
    b = rng.standard_normal(n)
    y = np.asarray(trisolve_ref(jnp.asarray(Lu), jnp.asarray(b), FP64,
                                lower=True, block=32))
    L = np.tril(Lu, -1) + np.eye(n)
    np.testing.assert_allclose(y, sla.solve_triangular(L, b, lower=True),
                               rtol=1e-12)
    x = np.asarray(trisolve_ref(jnp.asarray(Lu), jnp.asarray(b), FP64,
                                lower=False, block=32))
    np.testing.assert_allclose(x, sla.solve_triangular(np.triu(Lu), b),
                               rtol=1e-9)


def test_triangular_solvers_dispatch_to_blocked():
    """solve_unit_lower / solve_upper route through chop_trisolve at and
    above min_n, and stay strict below (bitwise check on both sides)."""
    rng = np.random.default_rng(8)
    n = 48
    Lu = jnp.asarray(tri_factors(n, rng), jnp.float32)
    b = jnp.asarray(rng.standard_normal(n), jnp.float32)
    pol = BlockingPolicy(min_n=48, trisolve_block=16)
    got = solve_unit_lower(Lu, b, BF16, backend=ORACLE, blocking=pol)
    want = trisolve_ref(Lu, b, BF16, lower=True, block=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Below the threshold the strict row loop answers.
    below = BlockingPolicy(min_n=49, trisolve_block=16)
    strict = solve_unit_lower(Lu, b, BF16, backend=ORACLE, blocking=below)
    plain = solve_unit_lower(Lu, b, BF16, backend=ORACLE,
                             blocking=STRICT_ONLY)
    np.testing.assert_array_equal(np.asarray(strict), np.asarray(plain))


# ---------------------------------------------------------------------------
# Pinned-contract chopped GEMM (backend.chop_matmul)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 16, 32), (40, 17, 23), (64, 64, 64)],
                         ids=["small", "ragged", "square"])
@pytest.mark.parametrize("fid", [FP32, BF16, FORMAT_ID["fp16"]])
def test_chop_matmul_bitexact_across_backends(fid, shape):
    M, K, N = shape
    rng = np.random.default_rng(M + K + fid)
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    got = PALLAS.chop_matmul(a, b, fid)
    want = ORACLE.chop_matmul(a, b, fid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The oracle follows the documented formula: lane-padded K, one
    # carrier dot, output rounding.
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(qgemm_ref(a, b, fid)))


def test_chop_matmul_bitexact_batched():
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((3, 48, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((3, 16, 48)), jnp.float32)
    got = jax.vmap(lambda x, y: qgemm_op(x, y, BF16, interpret=True))(a, b)
    want = jax.vmap(lambda x, y: qgemm_ref(x, y, BF16))(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# solve_upper division semantics: double rounding is intentional
# ---------------------------------------------------------------------------

def test_solve_upper_double_rounding_pinned():
    """The division path stores the numerator (one rounding) before the
    quotient (second rounding): chop(chop(y - s) / safe). Find inputs
    where single and double rounding differ, then pin the solver to the
    double-rounded value on both the strict and blocked paths."""
    rng = np.random.default_rng(17)
    # 1x1 upper systems: solve_upper reduces to the division semantics.
    vals = rng.uniform(1.0, 2.0, 4096)
    divs = rng.uniform(1.0, 2.0, 4096)
    y = jnp.asarray(vals)
    d = jnp.asarray(divs)
    double = chop(chop(y, BF16) / d, BF16)   # b chopped at entry, s = 0
    single = chop(y / d, BF16)
    diff = np.nonzero(np.asarray(double) != np.asarray(single))[0]
    assert diff.size > 0, "need a discriminating case"
    i = int(diff[0])
    Lu = jnp.asarray([[float(divs[i])]])
    got = solve_upper(Lu, jnp.asarray([float(vals[i])]), BF16)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(double[i]).reshape(1))
    # Blocked path: same double rounding inside the diagonal block.
    n = 32
    Lu_n = jnp.asarray(np.diag(divs[:n]) +
                       np.triu(rng.standard_normal((n, n)) * 0.1, 1),
                       jnp.float32)
    b_n = jnp.asarray(vals[:n], jnp.float32)
    blocked = trisolve_ref(Lu_n, b_n, BF16, lower=False, block=16)
    # Last row has no off-diagonal sum: exactly the division semantics.
    want_last = chop(chop(b_n[-1:], BF16) / Lu_n[-1, -1], BF16)
    np.testing.assert_array_equal(np.asarray(blocked)[-1:],
                                  np.asarray(want_last))


# ---------------------------------------------------------------------------
# Blocked-LU panel-width autotune (solvers/block_autotune)
# ---------------------------------------------------------------------------

def test_panel_autotune_picks_measured_candidate():
    from repro.solvers import BlockingPolicy, tuned_blocking
    from repro.solvers.block_autotune import sweep_lu_block
    base = BlockingPolicy(min_n=32, lu_block=16, trisolve_block=16)
    times = sweep_lu_block(64, candidates=(16, 32), trisolve_block=16,
                           repeats=1)
    assert set(times) == {16, 32}
    assert all(t > 0 for t in times.values())
    pol = tuned_blocking(64, base=base, candidates=(16, 32))
    assert pol.lu_block in (16, 32)
    assert pol.min_n == base.min_n and pol.trisolve_block == 16
    # Cached: the second lookup returns the identical committed policy.
    assert tuned_blocking(64, base=base, candidates=(16, 32)) is pol


def test_panel_autotune_skips_below_threshold_and_disabled():
    from repro.solvers import BlockingPolicy, STRICT_ONLY, tuned_blocking
    base = BlockingPolicy(min_n=256)
    assert tuned_blocking(64, base=base) == base        # strict path: no sweep
    assert tuned_blocking(512, base=STRICT_ONLY) == STRICT_ONLY


def test_task_opt_in_tunes_per_bucket():
    from repro.core import reduced_action_space
    from repro.data.matrices import randsvd_dense
    from repro.solvers import BlockingPolicy, IRConfig
    from repro.tasks import GMRESIRTask
    base = BlockingPolicy(min_n=32, lu_block=16, trisolve_block=16)
    cfg = IRConfig(tau=1e-6, i_max=3, m_max=8, blocking=base)
    space = reduced_action_space()
    systems = [randsvd_dense(30, 10.0, np.random.default_rng(3))]
    task = GMRESIRTask(systems, space, cfg, bucket_step=32, min_bucket=32,
                       tune_blocking=True)
    tuned = task.solver_cfg_for(cfg, 32)
    assert tuned.blocking.lu_block in (16, 32)          # <= bucket candidates
    # One tuned config per (cfg type, bucket): the jit key stays stable.
    assert task.solver_cfg_for(cfg, 32) is tuned
    # The tuned config actually drives the solve path.
    recs = task.solve_rows([task.prepare(systems[0])],
                           [space.actions[-1]], 2)
    assert len(recs) == 1 and recs[0].ok
