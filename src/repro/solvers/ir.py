"""GMRES-based iterative refinement (paper Alg. 2) with per-step precisions.

Action a = (u_f, u, u_g, u_r) — four runtime format ids:
  u_f : LU factorization (+ its use as the GMRES preconditioner's factors)
  u   : solution update x_{i+1} = x_i + z_i
  u_g : GMRES working precision (operator, MGS, Givens)
  u_r : residual computation r_i = b - A x_i

Stopping criteria (paper Eqs. 14-16):
  converged : ||z_i||_inf / ||x_{i+1}||_inf <= max(tau, u_work(u))
  stagnated : ||z_i||_inf / ||z_{i-1}||_inf >= stag_tol
  max-iter  : i >= i_max
plus an explicit failure path (LU overflow / zero pivot / non-finite GMRES).

x0 initialization: the paper's Alg. 2 line 2 uses x0 = U\\(L\\b); its
*reported* iteration counts (exactly 2.0 outer iterations for every FP64
baseline row of Tables 2/4/6) are only consistent with x0 = 0, where the
first "refinement" performs the initial solve through the preconditioned
GMRES. We default to x0 = 0 to match the paper's accounting and provide
init="lu" for the literal Alg. 2 variant.

Everything is jit-compatible with runtime format ids and vmappable over
(systems x actions) — the bandit sweeps a whole episode in one batched call.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from repro.precision import (FORMAT_ID, DoubleWord, dw, resolve_backend,
                             rounding_unit, tree_sum)

from .blocking import DEFAULT_BLOCKING, BlockingPolicy
from .carrier import carrier_residual, carrier_residual_dw
from .gmres import chop_mv, gmres_precond, gmres_precond_dw
from .lu import LUFactors, lu_factor_auto, lu_factor_dw
from .triangular import lu_solve


@dataclasses.dataclass(frozen=True)
class IRConfig:
    tau: float = 1e-6          # convergence tolerance (benchmark parameter)
    i_max: int = 10            # max outer (refinement) iterations
    m_max: int = 40            # max inner GMRES iterations
    tol_inner: float = 1e-4    # GMRES relative residual tolerance
    stag_tol: float = 0.9      # Eq. 15 stagnation threshold
    init: str = "zero"         # "zero" (paper accounting) | "lu" (Alg.2 l.2)
    # Blocked LU/trisolve engagement (DESIGN.md §6.4). Part of the
    # frozen config so it rides in the static jit key with the rest.
    blocking: BlockingPolicy = DEFAULT_BLOCKING


# Solver outcome status codes.
CONVERGED, STAGNATED, MAXITER, FAILED = 0, 1, 2, 3


class SolveStats(NamedTuple):
    ferr: jnp.ndarray          # normwise relative forward error (Eq. 17)
    nbe: jnp.ndarray           # normwise relative backward error (Eq. 17)
    n_outer: jnp.ndarray      # refinement iterations performed
    n_gmres: jnp.ndarray      # total inner GMRES iterations
    status: jnp.ndarray       # CONVERGED/STAGNATED/MAXITER/FAILED
    res_norm: jnp.ndarray     # final ||b - A x||_inf


def _inf_norm(v):
    return jnp.max(jnp.abs(v))


def _gmres_ir_impl(A, b, x_true, action, cfg, backend) -> SolveStats:
    dtype = A.dtype
    chop = backend.chop
    uf, u, ug, ur = action[0], action[1], action[2], action[3]

    lu = lu_factor_auto(A, uf, backend=backend, blocking=cfg.blocking)
    A_g = chop(A, ug)
    A_r = chop(A, ur)
    b_r = chop(b, ur)

    if cfg.init == "lu":
        x0 = lu_solve(lu.lu, lu.perm, b, uf, backend=backend,
                      blocking=cfg.blocking)
        x0 = jnp.where(jnp.isfinite(x0), x0, jnp.zeros_like(x0))
    else:
        x0 = jnp.zeros_like(b)

    u_work = rounding_unit(u, dtype)
    conv_tol = jnp.maximum(jnp.asarray(cfg.tau, dtype), u_work)

    def cond(state):
        *_, done = state
        return ~done

    def body(state):
        x, znorm_prev, i, n_gmres, status, done = state
        r = chop(b_r - chop_mv(A_r, x, ur, backend=backend), ur)
        gm = gmres_precond(A_g, lu.lu, lu.perm, r, ug,
                           m_max=cfg.m_max, tol=cfg.tol_inner,
                           backend=backend, blocking=cfg.blocking)
        z = chop(gm.z, u)
        x_new = chop(x + z, u)
        znorm = _inf_norm(z)
        xnorm = _inf_norm(x_new)
        i_new = i + 1

        converged = znorm <= conv_tol * xnorm
        stagnated = (i > 0) & (znorm >= cfg.stag_tol * znorm_prev)
        hit_max = i_new >= cfg.i_max
        failed = gm.fail | ~jnp.all(jnp.isfinite(x_new))

        status = jnp.where(
            failed, FAILED,
            jnp.where(converged, CONVERGED,
                      jnp.where(stagnated, STAGNATED,
                                jnp.where(hit_max, MAXITER, status))))
        done = converged | stagnated | hit_max | failed
        x_new = jnp.where(failed, x, x_new)
        return (x_new, znorm, i_new, n_gmres + gm.iters, status, done)

    init_state = (x0, jnp.asarray(jnp.inf, dtype), jnp.int32(0),
                  jnp.int32(0), jnp.int32(MAXITER), lu.fail)
    x, _, n_outer, n_gmres, status, _ = lax.while_loop(cond, body, init_state)
    status = jnp.where(lu.fail, FAILED, status)

    # Final metrics in the carrier (true fp64), Eq. 17, with the
    # executor-invariant residual schedule (see carrier_residual).
    res = carrier_residual(A, b, x)
    res_norm = _inf_norm(res)
    normA = jnp.max(tree_sum(jnp.abs(A), axis=1))
    ferr = _inf_norm(x - x_true) / _inf_norm(x_true)
    nbe = res_norm / (normA * _inf_norm(x) + _inf_norm(b))
    bad = ~jnp.isfinite(ferr)
    ferr = jnp.where(bad, jnp.asarray(jnp.inf, dtype), ferr)
    nbe = jnp.where(jnp.isfinite(nbe), nbe, jnp.asarray(jnp.inf, dtype))
    return SolveStats(ferr, nbe, n_outer, n_gmres, status, res_norm)


FP64_ID = FORMAT_ID["fp64"]


def dw_branches(actions):
    """(dw_lu, dw_gmres) of a flush on the double-word carrier: does some
    row factor at u_f = fp64, does some row run GMRES at u_g = fp64.
    `actions` is the flush's (rows, 4) format ids, numpy on the host (the
    `flush` span's args) or traced on the device (the `lax.cond`
    predicates): one rule for both. Pad rows repeat row 0, so they
    change neither answer."""
    return ((actions[..., 0] == FP64_ID).any(),
            (actions[..., 2] == FP64_ID).any())


def _gmres_ir_dw_row(A, b, x_true, action, LU, perm, lu_fail, dw_gmres,
                     cfg, bk) -> SolveStats:
    """One row of GMRES-IR on the double-word carrier (DESIGN.md §13).
    A, b, x_true and LU are `dw.DW` pairs. x, the update and the
    residual are DW, rounded to the row's runtime formats by `dw.chop`
    (the identity at fp64). GMRES at u_g <= fp32 runs `base`'s f32 path
    on the correctly rounded operands; at u_g = fp64 it runs
    `gmres_precond_dw`, behind a `lax.cond` on `dw_gmres`, the flush's
    unbatched predicate: under the batch's vmap the cond stays a cond,
    so a flush whose rows all have u_g <= fp32 traces the DW GMRES but
    never runs it. Each row takes the solver of its own u_g, so its
    answer does not depend on the other rows of its flush."""
    base = bk.base
    f32 = jnp.float32
    u, ug, ur = action[1], action[2], action[3]
    g64 = ug == FP64_ID
    A_g32 = dw.chop(A, ug).hi
    A_r = dw.chop(A, ur)
    sA_r = dw.split(A_r.hi)
    b_r = dw.chop(b, ur)
    zero = dw.zeros(b.hi.shape)
    conv_tol = jnp.maximum(jnp.asarray(cfg.tau, f32), rounding_unit(u, f32))

    def dw_solve(r):
        gm = gmres_precond_dw(A, LU, perm, dw.where(g64, r, zero),
                              m_max=cfg.m_max, tol=cfg.tol_inner)
        return gm.z, gm.iters, gm.fail

    def no_dw_solve(r):
        return zero, jnp.int32(0), jnp.asarray(True)

    def cond(state):
        *_, done = state
        return ~done

    def body(state):
        x, znorm_prev, i, n_gmres, status, done = state
        r = dw.chop(dw.sub(b_r, dw.chop(dw.matvec(A_r, x, sA_r), ur)), ur)
        # Rows at u_g = fp64 hand f32 GMRES a zero residual, the others
        # hand it to the DW GMRES: each solver stops at once on those.
        gm = gmres_precond(A_g32, LU.hi, perm, jnp.where(
            g64, jnp.zeros_like(r.hi), dw.chop(r, ug).hi), ug,
            m_max=cfg.m_max, tol=cfg.tol_inner, backend=base,
            blocking=cfg.blocking)
        zd, itd, faild = lax.cond(dw_gmres, dw_solve, no_dw_solve, r)
        z = dw.where(g64, zd, dw.lift(gm.z))
        iters = jnp.where(g64, itd, gm.iters)
        gfail = jnp.where(g64, faild, gm.fail)
        z = dw.chop(z, u)
        x_new = dw.chop(dw.add(x, z), u)
        znorm = _inf_norm(z.hi)
        xnorm = _inf_norm(x_new.hi)
        i_new = i + 1

        converged = znorm <= conv_tol * xnorm
        stagnated = (i > 0) & (znorm >= cfg.stag_tol * znorm_prev)
        hit_max = i_new >= cfg.i_max
        failed = gfail | ~jnp.all(jnp.isfinite(x_new.hi))

        status = jnp.where(
            failed, FAILED,
            jnp.where(converged, CONVERGED,
                      jnp.where(stagnated, STAGNATED,
                                jnp.where(hit_max, MAXITER, status))))
        done = converged | stagnated | hit_max | failed
        x_new = dw.where(failed, x, x_new)
        return (x_new, znorm, i_new, n_gmres + iters, status, done)

    init_state = (zero, jnp.asarray(jnp.inf, f32), jnp.int32(0),
                  jnp.int32(0), jnp.int32(MAXITER), lu_fail)
    x, _, n_outer, n_gmres, status, _ = lax.while_loop(cond, body,
                                                       init_state)
    status = jnp.where(lu_fail, FAILED, status)

    # Eq. 17 on the DW carrier: the residual and the error in DW, so
    # nbe and ferr have no f32 floor; the norms read the high words.
    res_norm = _inf_norm(carrier_residual_dw(A, b, x).hi)
    normA = jnp.max(tree_sum(jnp.abs(A.hi), axis=1))
    ferr = _inf_norm(dw.sub(x, x_true).hi) / _inf_norm(x_true.hi)
    nbe = res_norm / (normA * _inf_norm(x.hi) + _inf_norm(b.hi))
    inf = jnp.asarray(jnp.inf, f32)
    ferr = jnp.where(jnp.isfinite(ferr), ferr, inf)
    nbe = jnp.where(jnp.isfinite(nbe), nbe, inf)
    return SolveStats(ferr, nbe, n_outer, n_gmres, status, res_norm)


def _gmres_ir_dw_batch(A, b, x_true, actions, cfg, bk) -> SolveStats:
    """The batched solve on the double-word carrier. A, b and x_true
    arrive split by `dw.stack_host`: (rows, 2, ...) f32. The f32 LU of
    every row runs as today, on the operands rounded correctly to u_f.
    A flush with some row at u_f = fp64 (the all-fp64 arm) also runs
    `lu_factor_dw`, one row at a time under its own cond, so only those
    rows pay for it. Both flush predicates are computed here, outside
    the vmap, so each `lax.cond` stays a cond."""
    if cfg.init != "zero":
        raise ValueError("the double-word carrier starts from x0 = 0 "
                         f"(IRConfig.init='zero'), not {cfg.init!r}")
    base = bk.base
    A, b, x_true = dw.unstack(A), dw.unstack(b), dw.unstack(x_true)
    dw_lu, dw_gmres = dw_branches(actions)
    uf = actions[:, 0]
    f64_rows = uf == FP64_ID
    lu32 = jax.vmap(lambda Ai, fi: lu_factor_auto(
        dw.chop(Ai, fi).hi, fi, backend=base, blocking=cfg.blocking))(
            A, uf)

    def with_dw_lu():
        def one(args):
            Ai, is64 = args
            return lax.cond(is64, lu_factor_dw, lambda a: LUFactors(
                dw.zeros(a.hi.shape), jnp.arange(a.hi.shape[-1]),
                jnp.asarray(False)), Ai)

        lud = lax.map(one, (A, f64_rows))
        sel = f64_rows[:, None, None]
        return (dw.DW(jnp.where(sel, lud.lu.hi, lu32.lu),
                      jnp.where(sel, lud.lu.lo, 0)),
                jnp.where(f64_rows[:, None], lud.perm, lu32.perm),
                jnp.where(f64_rows, lud.fail, lu32.fail))

    def without_dw_lu():
        return dw.lift(lu32.lu), lu32.perm, lu32.fail

    LU, perm, lu_fail = lax.cond(dw_lu, with_dw_lu, without_dw_lu)
    return jax.vmap(lambda Ai, bi, xi, ai, Li, pi, fi: _gmres_ir_dw_row(
        Ai, bi, xi, ai, Li, pi, fi, dw_gmres, cfg, bk))(
            A, b, x_true, actions, LU, perm, lu_fail)


def _is_dw(backend) -> bool:
    return isinstance(backend, DoubleWord)


# The backend is resolved *before* tracing and passed as a value-hashed
# static argument: one executable per (shapes, cfg, backend), with the
# action's format ids still runtime data (DESIGN.md §3.4, §6.3). The
# jitted inner functions are module-level so tests can assert their
# compile-cache size stays at one across precision actions.
_gmres_ir_jit = partial(jax.jit, static_argnames=("cfg", "backend"))(
    _gmres_ir_impl)


@partial(jax.jit, static_argnames=("cfg", "backend"))
def _gmres_ir_batch_jit(A, b, x_true, actions, cfg, backend) -> SolveStats:
    if _is_dw(backend):
        return _gmres_ir_dw_batch(A, b, x_true, actions, cfg, backend)
    return jax.vmap(lambda Ai, bi, xi, ai:
                    _gmres_ir_impl(Ai, bi, xi, ai, cfg, backend)
                    )(A, b, x_true, actions)


def gmres_ir(A: jnp.ndarray, b: jnp.ndarray, x_true: jnp.ndarray,
             action: jnp.ndarray, cfg: IRConfig = IRConfig(),
             backend=None) -> SolveStats:
    """Solve A x = b with GMRES-IR under precision action (u_f, u, u_g, u_r).

    A: (n, n) carrier (float64 for the paper's host experiments; the
    pallas backend coerces to its f32 TPU carrier); action: int32[4]
    runtime format ids. `backend` selects the precision backend
    (DESIGN.md §6): an instance, a registry name, or None = default.
    """
    bk = resolve_backend(backend)
    if _is_dw(bk):
        out = gmres_ir_batch(*(np.asarray(a)[None]
                               for a in (A, b, x_true, action)),
                             cfg=cfg, backend=bk)
        return SolveStats(*(f[0] for f in out))
    A, b, x_true = bk.coerce(jnp.asarray(A), jnp.asarray(b),
                             jnp.asarray(x_true))
    return _gmres_ir_jit(A, b, x_true, action, cfg, bk)


def gmres_ir_batch(A, b, x_true, actions, cfg: IRConfig = IRConfig(),
                   backend=None) -> SolveStats:
    """Batched (vmap) GMRES-IR: one episode sweep = one call."""
    bk = resolve_backend(backend)
    if _is_dw(bk):
        return _gmres_ir_batch_jit(*_dw_args(A, b, x_true, actions),
                                   cfg, bk)
    A, b, x_true = bk.coerce(jnp.asarray(A), jnp.asarray(b),
                             jnp.asarray(x_true))
    return _gmres_ir_batch_jit(A, b, x_true, actions, cfg, bk)


def _dw_args(A, b, x_true, actions):
    """The batch's operands on the double-word carrier, outside the jit
    boundary: float64 rows split into (hi, lo) f32 pairs by
    `dw.stack_host`, in numpy when they are host arrays, so the transfer
    carries no f64. Rows already split (one axis more than a batch of
    matrices) pass as they are."""
    if np.ndim(A) == 3:
        A, b, x_true = (dw.stack_host(a) for a in (A, b, x_true))
    return (jnp.asarray(A), jnp.asarray(b), jnp.asarray(x_true),
            jnp.asarray(actions))


def gmres_ir_batch_lowerable(cfg: IRConfig = IRConfig(), backend=None):
    """`gmres_ir_batch` in `core.executor.LowerableCall` form: the same
    eager carrier coercion (`prepare`) around the same module-level
    jitted entry point, but AOT-compilable — `lower().compile()` per
    shape — and value-keyed by (entry point, cfg, backend), so every
    task and call site running this program shares one executable per
    shape (DESIGN.md §12).

    `prepare` takes rows in `bk.host_dtype`, the dtype the flush path
    stacks them in (`tasks.base.stack_fixed`): f32 on the Pallas
    backend, so `coerce` casts nothing and no f64 buffer reaches the
    device; float64 on `DoubleWord`, split here into (hi, lo) f32
    pairs; as given on the jnp backend."""
    from repro.core.executor import LowerableCall
    bk = resolve_backend(backend)

    def prepare(A, b, x_true, actions):
        if _is_dw(bk):
            return _dw_args(A, b, x_true, actions)
        A, b, x_true = bk.coerce(jnp.asarray(A), jnp.asarray(b),
                                 jnp.asarray(x_true))
        return A, b, x_true, jnp.asarray(actions)

    return LowerableCall(_gmres_ir_batch_jit,
                         (("cfg", cfg), ("backend", bk)), prepare)
