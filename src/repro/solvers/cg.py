"""Preconditioned-CG iterative refinement (CG-IR) with per-step precisions.

The second instantiation of the autotuning recipe (cf. "Mixed-Precision
CG Solvers with RL-Driven Precision Tuning", arXiv 2504.14268): the same
outer iterative-refinement loop as `ir.gmres_ir`, but the correction
equation A z = r is solved by LU-preconditioned conjugate gradients in
the working precision instead of GMRES. Intended for SPD systems (the
sparse SPD generator in `data.matrices`); a breakdown of the CG
recurrence (non-positive curvature p^T A p, non-finite iterates) maps to
the explicit failure path, exactly like an overflowed LU in GMRES-IR.

Action a = (u_f, u, u_g, u_r) — four runtime format ids with the same
roles as GMRES-IR:
  u_f : LU factorization (used as the CG preconditioner M = LU)
  u   : solution update x_{i+1} = x_i + z_i
  u_g : CG working precision (matvec, preconditioner solves, dots)
  u_r : residual computation r_i = b - A x_i

The outer loop is GMRES-IR's own (`ir.refine`, Eqs. 14-16): update-norm
convergence, stagnation, max outer iterations, explicit failure. This
module holds the inner solve, `pcg`, and the entry points.

Everything is jit-compatible with runtime format ids and vmappable over
(systems x actions) — `cg_ir_batch` is the fixed-shape batched entry
point used by `repro.tasks.cg_ir.CGIRTask`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from repro.precision import resolve_backend, tree_sum

from .blocking import DEFAULT_BLOCKING, BlockingPolicy, resolve_blocking
from .carrier import carrier_norm
from .ir import CONVERGED, FAILED, MAXITER, STAGNATED, ir_row
from .triangular import lu_solve


@dataclasses.dataclass(frozen=True)
class CGConfig:
    tau: float = 1e-6          # convergence tolerance (benchmark parameter)
    i_max: int = 10            # max outer (refinement) iterations
    m_max: int = 50            # max inner CG iterations
    tol_inner: float = 1e-4    # CG relative residual tolerance
    stag_tol: float = 0.9      # stagnation threshold on ||z_i||/||z_{i-1}||
    # Blocked LU/trisolve engagement (DESIGN.md §6.4), static jit key.
    blocking: BlockingPolicy = DEFAULT_BLOCKING


class CGStats(NamedTuple):
    ferr: jnp.ndarray          # normwise relative forward error (Eq. 17)
    nbe: jnp.ndarray           # normwise relative backward error (Eq. 17)
    n_outer: jnp.ndarray       # refinement iterations performed
    n_cg: jnp.ndarray          # total inner CG iterations
    status: jnp.ndarray        # CONVERGED/STAGNATED/MAXITER/FAILED
    res_norm: jnp.ndarray      # final ||b - A x||_inf


class PCGResult(NamedTuple):
    z: jnp.ndarray             # solution update
    iters: jnp.ndarray         # inner iterations performed
    fail: jnp.ndarray          # breakdown (non-SPD curvature / non-finite)


def _dot(a, b, fmt_id, chop):
    """Dot product with format-rounded products, carrier accumulation
    (order pinned by the fixed pairwise tree — DESIGN.md §7.3)."""
    return chop(tree_sum(chop(a * b, fmt_id)), fmt_id)


def pcg(A_g: jnp.ndarray, LU: jnp.ndarray, perm: jnp.ndarray,
        r: jnp.ndarray, fmt_g, *, m_max: int, tol: float,
        backend=None, blocking=None) -> PCGResult:
    """LU-preconditioned CG on A z = r, entirely in precision u_g.

    A_g: the system matrix pre-chopped to u_g; LU/perm: chopped factors
    of A in u_f, used as the (fixed) preconditioner.
    """
    bk = resolve_backend(backend)
    pol = resolve_blocking(blocking)
    A_g, LU, r = bk.coerce(jnp.asarray(A_g), jnp.asarray(LU),
                           jnp.asarray(r))
    chop = bk.chop
    dtype = r.dtype
    r0 = chop(r, fmt_g)
    beta0 = carrier_norm(r0)
    ok0 = jnp.isfinite(beta0) & (beta0 > 0)
    y0 = lu_solve(LU, perm, r0, fmt_g, backend=bk, blocking=pol)
    rho0 = _dot(r0, y0, fmt_g, chop)
    z0 = jnp.zeros_like(r0)

    def cond(state):
        *_, j, done, _fail = state
        return (~done) & (j < m_max)

    def body(state):
        z, rin, p, rho, j, done, fail = state
        q = bk.chop_mv(A_g, p, fmt_g)
        pq = _dot(p, q, fmt_g, chop)
        # Non-positive curvature: A (or the chopped recurrence) stopped
        # behaving SPD — a genuine CG breakdown, not mere stagnation.
        breakdown = (pq <= 0) | ~jnp.isfinite(pq)
        pq_safe = jnp.where(breakdown, jnp.ones((), dtype), pq)
        alpha = chop(rho / pq_safe, fmt_g)
        z_new = chop(z + chop(alpha * p, fmt_g), fmt_g)
        rin_new = chop(rin - chop(alpha * q, fmt_g), fmt_g)
        res = carrier_norm(rin_new)
        y = lu_solve(LU, perm, rin_new, fmt_g, backend=bk, blocking=pol)
        rho_new = _dot(rin_new, y, fmt_g, chop)
        rho_safe = jnp.where(rho == 0, jnp.ones((), dtype), rho)
        beta = chop(rho_new / rho_safe, fmt_g)
        p_new = chop(y + chop(beta * p, fmt_g), fmt_g)

        nonfinite = ~(jnp.all(jnp.isfinite(z_new)) & jnp.isfinite(res)
                      & jnp.isfinite(rho_new))
        fail_now = breakdown | nonfinite
        converged = res <= tol * beta0
        z_new = jnp.where(fail_now, z, z_new)
        return (z_new, rin_new, p_new, rho_new, j + 1,
                fail_now | converged, fail | fail_now)

    init = (z0, r0, y0, rho0, jnp.int32(0), ~ok0, ~ok0)
    z, _, _, _, j, _, fail = lax.while_loop(cond, body, init)
    fail = fail | ~jnp.all(jnp.isfinite(z))
    z = jnp.where(fail, jnp.zeros_like(z), z)
    return PCGResult(z, j, fail)


# Backend resolved before tracing, passed value-hashed static: one
# executable per (shapes, cfg, backend), format ids runtime data
# (DESIGN.md §3.4, §6.3). A module-level jit so tests can assert the
# compile-cache stays at one across precision actions. The outer loop
# is `ir.refine`; `pcg` is the step's inner solve.
@partial(jax.jit, static_argnames=("cfg", "backend"))
def _cg_ir_batch_jit(A, b, x_true, actions, cfg, backend) -> CGStats:
    return CGStats(*jax.vmap(lambda Ai, bi, xi, ai: ir_row(
        pcg, Ai, bi, xi, ai, cfg, backend))(A, b, x_true, actions))


def cg_ir(A: jnp.ndarray, b: jnp.ndarray, x_true: jnp.ndarray,
          action: jnp.ndarray, cfg: CGConfig = CGConfig(),
          backend=None) -> CGStats:
    """Solve A x = b with CG-IR under precision action (u_f, u, u_g, u_r).

    A: (n, n) carrier (SPD; float64 on the host, f32 when the pallas
    backend coerces); action: int32[4] runtime format ids. `backend`
    selects the precision backend (DESIGN.md §6). Runs as a batch of one
    through `cg_ir_batch`."""
    out = cg_ir_batch(*(np.asarray(a)[None]
                        for a in (A, b, x_true, action)),
                      cfg=cfg, backend=backend)
    return CGStats(*(f[0] for f in out))


def _prepare(bk, A, b, x_true, actions):
    """The batch's operands for `_cg_ir_batch_jit`, cast to the
    backend's carrier outside the jit boundary."""
    A, b, x_true = bk.coerce(jnp.asarray(A), jnp.asarray(b),
                             jnp.asarray(x_true))
    return A, b, x_true, jnp.asarray(actions)


def cg_ir_batch(A, b, x_true, actions, cfg: CGConfig = CGConfig(),
                backend=None) -> CGStats:
    """Batched (vmap) CG-IR: one fixed-shape chunk = one call."""
    bk = resolve_backend(backend)
    return _cg_ir_batch_jit(*_prepare(bk, A, b, x_true, actions), cfg, bk)


def cg_ir_batch_lowerable(cfg: CGConfig = CGConfig(), backend=None):
    """`cg_ir_batch` in `core.executor.LowerableCall` form — same eager
    coercion, same jitted entry point, AOT-compilable and value-keyed
    for cross-task executable dedupe (DESIGN.md §12)."""
    from repro.core.executor import LowerableCall
    bk = resolve_backend(backend)
    return LowerableCall(_cg_ir_batch_jit,
                         (("cfg", cfg), ("backend", bk)),
                         partial(_prepare, bk))


# Re-exported status codes (shared convention with ir.py / core.task).
__all__ = ["CGConfig", "CGStats", "PCGResult", "pcg", "cg_ir",
           "cg_ir_batch", "cg_ir_batch_lowerable",
           "CONVERGED", "STAGNATED", "MAXITER", "FAILED"]
