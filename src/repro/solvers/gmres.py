"""Left-preconditioned MGS-GMRES in emulated precision u_g.

Solves M^{-1} A z = M^{-1} r with M = LU (chopped factors from lu.py),
entirely in precision u_g: the operator application (matvec + two triangular
solves), the modified Gram-Schmidt orthogonalization, and the Givens
least-squares recurrence are all executed with op-level rounding to the
runtime format id. Accumulations happen in the carrier dtype (MXU-style),
see DESIGN.md §3.5.

The hot-path rounding ops dispatch through a precision backend
(DESIGN.md §6): `chop_mv` is the backend's fused chopped matvec
(kernels/qmatmul on the pallas backend) and standalone roundings go
through `backend.chop` (kernels/chop for large arrays). The backend is
resolved before tracing and is a value-hashed static, so format ids stay
runtime data and precision actions never recompile (DESIGN.md §3.4).

Non-restarted, with a while_loop bounded by m_max; the residual estimate is
the standard |g_{j+1}| Givens recurrence, relative to the preconditioned
initial residual norm beta.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from repro.precision import dw, resolve_backend, tree_sum

from .blocking import resolve_blocking
from .carrier import carrier_norm
from .triangular import solve_unit_lower, solve_upper


class GMRESResult(NamedTuple):
    z: jnp.ndarray        # solution update
    iters: jnp.ndarray    # inner iterations performed
    res_rel: jnp.ndarray  # final relative (preconditioned) residual estimate
    fail: jnp.ndarray     # non-finite breakdown


def chop_mv(A: jnp.ndarray, v: jnp.ndarray, fmt_id,
            backend=None) -> jnp.ndarray:
    """Fused chopped matvec: operands rounded to the format, accumulation
    in the carrier, result rounded (FMA/MXU semantics — the matvec
    instantiation of kernels/qmatmul; see DESIGN.md §6.2). Operands are
    coerced to the backend's carrier dtype (no-op on the jnp oracle and
    on pre-coerced arrays)."""
    bk = resolve_backend(backend)
    A, v = bk.coerce(jnp.asarray(A), jnp.asarray(v))
    return bk.chop_mv(A, v, fmt_id)


def _precond(LU, perm, v, fmt_id, backend, blocking=None):
    # Preconditioner application M^{-1} v: the two triangular solves
    # take the blocked `chop_trisolve` path above the size threshold
    # (DESIGN.md §6.4) — this pair dominates GMRES-IR wall time.
    y = solve_unit_lower(LU, v[perm], fmt_id, backend=backend,
                         blocking=blocking)
    return solve_upper(LU, y, fmt_id, backend=backend, blocking=blocking)


def gmres_precond(A_g: jnp.ndarray, LU: jnp.ndarray, perm: jnp.ndarray,
                  r: jnp.ndarray, fmt_g, *, m_max: int,
                  tol: float, backend=None,
                  blocking=None) -> GMRESResult:
    """A_g: the system matrix pre-chopped to u_g. r: outer residual."""
    bk = resolve_backend(backend)
    pol = resolve_blocking(blocking)
    A_g, LU, r = bk.coerce(jnp.asarray(A_g), jnp.asarray(LU),
                           jnp.asarray(r))
    chop = bk.chop
    n = r.shape[-1]
    dtype = r.dtype
    zero = jnp.zeros((), dtype)

    def apply_op(v):
        return _precond(LU, perm, bk.chop_mv(A_g, v, fmt_g), fmt_g, bk,
                        pol)

    rhat = _precond(LU, perm, chop(r, fmt_g), fmt_g, bk, pol)
    # Unrounded carrier norms take the pinned square-then-sum schedule
    # (solvers/carrier.py) so their bits are executor-invariant.
    beta = carrier_norm(rhat)
    ok0 = jnp.isfinite(beta) & (beta > 0)
    beta_safe = jnp.where(ok0, beta, jnp.ones((), dtype))
    v0 = chop(rhat / beta_safe, fmt_g)

    V = jnp.zeros((m_max + 1, n), dtype).at[0].set(jnp.where(ok0, v0, zero))
    R = jnp.zeros((m_max + 1, m_max), dtype)
    cs = jnp.zeros((m_max,), dtype)
    sn = jnp.zeros((m_max,), dtype)
    g = jnp.zeros((m_max + 1,), dtype).at[0].set(beta)

    def cond(state):
        *_, j, done = state
        return (~done) & (j < m_max)

    def body(state):
        V, R, cs, sn, g, res_prev, j, done = state
        w = apply_op(V[j])

        def mgs(i, carry):
            w, h = carry
            vi = V[i]
            hij = chop(tree_sum(chop(w * vi, fmt_g)), fmt_g)
            w = chop(w - chop(hij * vi, fmt_g), fmt_g)
            return w, h.at[i].set(hij)

        w, h = lax.fori_loop(0, j + 1, mgs,
                             (w, jnp.zeros((m_max + 1,), dtype)))
        hn = carrier_norm(w)
        happy = hn <= jnp.asarray(1e-300 if dtype == jnp.float64 else 1e-30,
                                  dtype)
        hn_safe = jnp.where(happy, jnp.ones((), dtype), hn)
        V = V.at[j + 1].set(jnp.where(happy, jnp.zeros_like(w),
                                      chop(w / hn_safe, fmt_g)))
        h = h.at[j + 1].set(hn)

        def rot(i, h):
            hi, hi1 = h[i], h[i + 1]
            h = h.at[i].set(chop(cs[i] * hi + sn[i] * hi1, fmt_g))
            return h.at[i + 1].set(chop(-sn[i] * hi + cs[i] * hi1, fmt_g))

        h = lax.fori_loop(0, j, rot, h)
        hj, hj1 = h[j], h[j + 1]
        denom = jnp.sqrt(hj * hj + hj1 * hj1)
        dsafe = jnp.where(denom == 0, jnp.ones((), dtype), denom)
        c, s = hj / dsafe, hj1 / dsafe
        cs = cs.at[j].set(c)
        sn = sn.at[j].set(s)
        h = h.at[j].set(chop(denom, fmt_g)).at[j + 1].set(zero)
        R = R.at[:, j].set(h)
        gj = g[j]
        g = g.at[j].set(chop(c * gj, fmt_g)).at[j + 1].set(chop(-s * gj, fmt_g))

        res = jnp.abs(g[j + 1])
        fin = jnp.isfinite(res) & jnp.all(jnp.isfinite(h))
        # Stall cut: a useless preconditioner (e.g. overflowed low-precision
        # LU on an ill-conditioned system) makes the residual plateau; give
        # up once per-iteration reduction falls under 5% past a warmup.
        stalled = (j >= 4) & (res > 0.95 * res_prev)
        done = happy | (res <= tol * beta) | stalled | ~fin
        return V, R, cs, sn, g, res, j + 1, done

    init = (V, R, cs, sn, g, jnp.asarray(jnp.inf, dtype), jnp.int32(0), ~ok0)
    V, R, cs, sn, g, _, j, done = lax.while_loop(cond, body, init)

    # Back-substitute R y = g on the leading j x j block.
    def back(i, y):
        row = m_max - 1 - i
        rrow = R[row]
        prods = chop(rrow * y, fmt_g)
        mask = jnp.arange(m_max) > row
        ssum = tree_sum(jnp.where(mask, prods, zero))
        diag = rrow[row]
        dsafe = jnp.where(diag == 0, jnp.ones((), dtype), diag)
        yi = chop(chop(g[row] - ssum, fmt_g) / dsafe, fmt_g)
        return y.at[row].set(jnp.where(row < j, yi, zero))

    y = lax.fori_loop(0, m_max, back, jnp.zeros((m_max,), dtype))
    z = chop(tree_sum(chop(V[:m_max] * y[:, None], fmt_g), axis=0), fmt_g)

    res_rel = jnp.abs(g[j]) / beta_safe
    fail = ~ok0 | ~jnp.all(jnp.isfinite(z))
    z = jnp.where(fail, jnp.zeros_like(z), z)
    return GMRESResult(z, j, res_rel, fail)


def gmres_precond_dw(A_g, LU, perm: jnp.ndarray, r, *, m_max: int,
                     tol: float) -> GMRESResult:
    """`gmres_precond` at u_g = fp64 on the double-word carrier
    (DESIGN.md §13): `A_g`, `LU` and `r` are `dw.DW` pairs, and so is
    every quantity the f32 path rounds to u_g. Rounding to fp64 is the
    identity on the pair, so each operation is DW-exact and stored as it
    is: the operator (DW matvec, the preconditioner's two DW
    substitutions), MGS, the Givens recurrence and the back-substitution,
    as the reference does them at the carrier's 48 bits. Convergence
    tests read the high words. `z` is a DW pair; a zero `r` returns at
    once (the rows of a batch that do not run this path)."""
    n = r.hi.shape[-1]
    f32 = jnp.float32
    one = dw.lift(f32(1))
    sA = dw.split(A_g.hi)
    F = dw.lu_factors(LU)
    zn = dw.zeros((n,))

    def safe(x, ok):
        return dw.tmap(lambda a: jnp.broadcast_to(a, (n,)),
                       dw.where(ok, x, one))

    def cond(state):
        *_, j, done = state
        return (~done) & (j < m_max)

    def start(w, state):
        # Step j = -1: w = M^-1 r gives beta, v_0 and g_0.
        V, R, cs, sn, g, _, res_prev, _, _ = state
        beta = dw.norm2(w)
        ok0 = jnp.isfinite(beta.hi) & (beta.hi > 0)
        V = dw.put(V, 0, dw.where(ok0, dw.div(w, safe(beta, ok0)), zn))
        return (V, R, cs, sn, dw.put(g, 0, beta), beta, res_prev,
                jnp.int32(0), ~ok0)

    def body(state):
        j = state[-2]
        # One call site of the operator and the preconditioner, so the
        # program holds one copy of the substitution: step -1 applies
        # M^-1 to r, every later step to A v_j.
        u = dw.where(j < 0, r, dw.matvec(
            A_g, dw.get(state[0], jnp.maximum(j, 0)), sA))
        w = dw.lu_solve(F, dw.tmap(lambda a: a[perm], u))
        return lax.cond(j < 0, start, arnoldi, w, state)

    def arnoldi(w, state):
        V, R, cs, sn, g, beta, res_prev, j, done = state

        def mgs(i, carry):
            w, h = carry
            vi = dw.get(V, i)
            hij = dw.dot(w, vi)
            w = dw.sub(w, dw.scale(vi, hij))
            return w, dw.put(h, i, hij)

        w, h = lax.fori_loop(0, j + 1, mgs, (w, dw.zeros((m_max + 1,))))
        hn = dw.norm2(w)
        happy = hn.hi <= f32(1e-30)
        vnext = dw.div(w, safe(hn, ~happy))
        V = dw.put(V, j + 1, dw.where(happy, zn, vnext))
        h = dw.put(h, j + 1, hn)

        def rot(i, h):
            hi, hi1 = dw.get(h, i), dw.get(h, i + 1)
            c, s = dw.get(cs, i), dw.get(sn, i)
            h = dw.put(h, i, dw.add(dw.mul(c, hi), dw.mul(s, hi1)))
            return dw.put(h, i + 1, dw.sub(dw.mul(c, hi1), dw.mul(s, hi)))

        h = lax.fori_loop(0, j, rot, h)
        hj, hj1 = dw.get(h, j), dw.get(h, j + 1)
        denom = dw.sqrt(dw.add(dw.mul(hj, hj), dw.mul(hj1, hj1)))
        dsafe = dw.where(denom.hi == 0, one, denom)
        c, s = dw.div(hj, dsafe), dw.div(hj1, dsafe)
        cs = dw.put(cs, j, c)
        sn = dw.put(sn, j, s)
        h = dw.put(dw.put(h, j, denom), j + 1, dw.lift(f32(0)))
        R = dw.put(R, j, h, axis=1)
        gj = dw.get(g, j)
        g = dw.put(dw.put(g, j, dw.mul(c, gj)), j + 1,
                   dw.neg(dw.mul(s, gj)))

        res = jnp.abs(dw.get(g, j + 1).hi)
        fin = jnp.isfinite(res) & jnp.all(jnp.isfinite(h.hi))
        stalled = (j >= 4) & (res > 0.95 * res_prev)
        done = happy | (res <= tol * beta.hi) | stalled | ~fin
        return V, R, cs, sn, g, beta, res, j + 1, done

    init = (dw.zeros((m_max + 1, n)), dw.zeros((m_max + 1, m_max)),
            dw.zeros((m_max,)), dw.zeros((m_max,)), dw.zeros((m_max + 1,)),
            dw.zeros(()), jnp.asarray(jnp.inf, f32), jnp.int32(-1),
            jnp.asarray(False))
    V, R, cs, sn, g, beta, _, j, done = lax.while_loop(cond, body, init)
    ok0 = jnp.isfinite(beta.hi) & (beta.hi > 0)
    beta_safe = dw.where(ok0, beta, one)

    # Back-substitute R y = g on the leading j x j block.
    idx = jnp.arange(m_max)

    def back(i, y):
        row = m_max - 1 - i
        rrow = dw.get(R, row)
        prods = dw.where(idx > row, dw.mul(rrow, y), dw.zeros((m_max,)))
        diag = dw.get(rrow, row)
        dsafe = dw.where(diag.hi == 0, one, diag)
        yi = dw.div(dw.sub(dw.get(g, row), dw.sum(prods)), dsafe)
        return dw.put(y, row, dw.where(row < j, yi, dw.lift(f32(0))))

    y = lax.fori_loop(0, m_max, back, dw.zeros((m_max,)))
    Vm = dw.tmap(lambda a: a[:m_max], V)
    z = dw.sum(dw.mul(Vm, dw.tmap(
        lambda a: jnp.broadcast_to(a[:, None], (m_max, n)), y)), axis=0)

    res_rel = jnp.abs(dw.get(g, j).hi) / beta_safe.hi
    fail = ~ok0 | ~jnp.all(jnp.isfinite(z.hi))
    z = dw.where(fail, dw.zeros((n,)), z)
    return GMRESResult(z, j, res_rel, fail)
