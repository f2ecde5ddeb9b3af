"""LU factorization with partial pivoting, in emulated precision u_f.

Strict mode (default, paper-faithful) mirrors Carson–Higham-style chopped
simulation: one rank-1 trailing update per column, with multiplication
results and subtraction results rounded to the target format; accumulation
of the (single) product happens in the carrier. The format id is runtime
data, so one compiled factorization serves every precision action.

Blocked mode (`lu_factor_blocked`) is the beyond-paper performance variant:
panels of `block` columns are factored strictly (partial pivoting restricted
to the panel), the panel's U12 row block is formed by a strict block
forward substitution, and the trailing update A22 -= L21 @ U12 is ONE
fused chopped GEMM dispatched through `backend.chop_matmul` (operands in
format, carrier accumulation — the semantics of tensor-core / MXU
mixed-precision GEMM hardware). The GEMM's lane-padded single-K-block
reduction contract keeps the jnp and pallas backends bit-identical
(DESIGN.md §6.2); everything else in the factorization is shared trace.
Sizes that are not a block multiple are identity-padded internally —
the padded tail factors trivially (L = U = I) and never couples back.

`lu_factor_auto` picks the path by size: blocked at
`blocking.min_n` and above, strict below (DESIGN.md §6.4). The outer
block loop is unrolled in Python (`n` is static at trace time), so every
panel/trailing slice is static and XLA sees O(n * block) panel work plus
one GEMM per panel instead of the strict path's O(n^2)-per-column masked
updates — this is what makes the factorization phase run at hardware
speed while the format id stays runtime data.

Failure signalling (the paper's `f_penalty` failure source): a zero pivot or
non-finite entry (overflow in a narrow format) sets `fail`; downstream code
short-circuits and the reward assigns the failure penalty.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.precision import dw, resolve_backend

from .blocking import resolve_blocking


class LUFactors(NamedTuple):
    lu: jnp.ndarray       # combined: strictly-lower L (unit diag), upper U
    perm: jnp.ndarray     # row permutation: P A = L U  with  (PA)[i] = A[perm[i]]
    fail: jnp.ndarray     # bool: zero pivot or non-finite (overflow) factor


def lu_factor(A: jnp.ndarray, fmt_id, backend=None) -> LUFactors:
    """Chopped right-looking LU with partial pivoting. A: (n, n) carrier."""
    chop = resolve_backend(backend).chop
    n = A.shape[-1]
    rows = jnp.arange(n)
    A0 = chop(A, fmt_id)

    def step(k, carry):
        A, perm, pivmin = carry
        col = jnp.take(A, k, axis=1)
        mag = jnp.where(rows >= k, jnp.abs(col), -jnp.inf)
        p = jnp.argmax(mag)
        # Swap rows k <-> p (A and the permutation record).
        rk, rp = A[k], A[p]
        A = A.at[k].set(rp).at[p].set(rk)
        ek, ep = perm[k], perm[p]
        perm = perm.at[k].set(ep).at[p].set(ek)

        pivot = A[k, k]
        pivmin = jnp.minimum(pivmin, jnp.abs(pivot))
        safe = jnp.where(pivot == 0, jnp.ones((), A.dtype), pivot)
        col = jnp.take(A, k, axis=1)
        factors = jnp.where(rows > k, chop(col / safe, fmt_id),
                            jnp.zeros((), A.dtype))
        rowk = A[k]
        prod = chop(factors[:, None] * rowk[None, :], fmt_id)
        upd = (rows[:, None] > k) & (rows[None, :] > k)
        A = jnp.where(upd, chop(A - prod, fmt_id), A)
        A = A.at[:, k].set(jnp.where(rows > k, factors, col))
        return A, perm, pivmin

    A1, perm, pivmin = lax.fori_loop(
        0, n, step, (A0, rows, jnp.asarray(jnp.inf, A.dtype)))
    fail = (pivmin == 0) | ~jnp.all(jnp.isfinite(A1))
    return LUFactors(A1, perm, fail)


def lu_factor_blocked(A: jnp.ndarray, fmt_id, block: int = 64,
                      backend=None) -> LUFactors:
    """Blocked variant: strict panel factorization + one fused chopped-GEMM
    trailing update per panel, dispatched through `backend.chop_matmul`
    (MXU semantics, bit-identical across backends — DESIGN.md §6.2/§6.4).
    Pivoting is restricted to the panel (standard blocked partial
    pivoting). Sizes that are not a block multiple are identity-padded
    internally; the returned factors are sliced back to (n, n)."""
    from repro.kernels.trisolve.ref import identity_pad

    bk = resolve_backend(backend)
    chop = bk.chop
    n = A.shape[-1]
    n_pad = -(-n // block) * block
    # Identity tail (shared convention with the blocked trisolve):
    # factors trivially (pivot 1, zero updates) and never couples back
    # into the leading n x n factorization.
    A = identity_pad(A, n_pad)
    rows = jnp.arange(n_pad)
    A0 = chop(A, fmt_id)
    carry = (A0, rows, jnp.asarray(jnp.inf, A.dtype))

    def make_panel_col(k0):
        # Strict rank-1 elimination of column k, with the update sliced
        # to the static panel window [k0, k0 + block): O(n * block) per
        # column instead of the strict path's O(n^2).
        pcols = k0 + jnp.arange(block)

        def panel_col(k, carry):
            A, perm, pivmin = carry
            col = jnp.take(A, k, axis=1)
            mag = jnp.where(rows >= k, jnp.abs(col), -jnp.inf)
            p = jnp.argmax(mag)
            rk, rp = A[k], A[p]
            A = A.at[k].set(rp).at[p].set(rk)
            ek, ep = perm[k], perm[p]
            perm = perm.at[k].set(ep).at[p].set(ek)
            pivot = A[k, k]
            pivmin = jnp.minimum(pivmin, jnp.abs(pivot))
            safe = jnp.where(pivot == 0, jnp.ones((), A.dtype), pivot)
            col = jnp.take(A, k, axis=1)
            factors = jnp.where(rows > k, chop(col / safe, fmt_id),
                                jnp.zeros((), A.dtype))
            panel = lax.slice(A, (0, k0), (n_pad, k0 + block))
            rowk = lax.dynamic_slice(panel, (k, 0), (1, block))
            prod = chop(factors[:, None] * rowk, fmt_id)
            upd = (rows[:, None] > k) & (pcols[None, :] > k)
            panel = jnp.where(upd, chop(panel - prod, fmt_id), panel)
            A = lax.dynamic_update_slice(A, panel, (0, k0))
            A = A.at[:, k].set(jnp.where(rows > k, factors, col))
            return A, perm, pivmin

        return panel_col

    # The block loop is unrolled in Python (n is static at trace time),
    # so every panel/trailing slice below is static-shaped.
    for k0 in range(0, n_pad, block):
        carry = lax.fori_loop(k0, k0 + block, make_panel_col(k0), carry)
        k1 = k0 + block
        m = n_pad - k1
        if m == 0:
            continue
        A1, perm, pivmin = carry
        tri = jnp.tril(jnp.ones((block, block), bool), -1)
        Lpan = jnp.where(tri, A1[k0:k1, k0:k1], jnp.zeros((), A1.dtype))
        A12 = A1[k0:k1, k1:]

        # U12 = (I + Lpan)^{-1} A12 by strict block forward substitution
        # (shared trace on every backend: plain jnp + bit-exact chop).
        def tri_row(i, U12):
            lrow = lax.dynamic_slice(Lpan, (i, 0), (1, block))
            acc = chop(jnp.dot(lrow, U12,
                               precision=lax.Precision.HIGHEST), fmt_id)
            new = chop(lax.dynamic_slice(A12, (i, 0), (1, m)) - acc,
                       fmt_id)
            return lax.dynamic_update_slice(U12, new, (i, 0))

        U12 = lax.fori_loop(0, block, tri_row,
                            jnp.zeros((block, m), A1.dtype))
        # Trailing update: A22 -= L21 @ U12 as ONE fused chopped GEMM
        # through the backend (lane-padded K contract, DESIGN.md §6.2).
        prod = bk.chop_matmul(A1[k1:, k0:k1], U12, fmt_id)
        A22 = chop(A1[k1:, k1:] - prod, fmt_id)
        A1 = A1.at[k0:k1, k1:].set(U12).at[k1:, k1:].set(A22)
        carry = (A1, perm, pivmin)

    A1, perm, pivmin = carry
    A1, perm = A1[:n, :n], perm[:n]
    fail = (pivmin == 0) | ~jnp.all(jnp.isfinite(A1))
    return LUFactors(A1, perm, fail)


def lu_factor_dw(A) -> LUFactors:
    """LU with partial pivoting on the double-word carrier: the paper's
    u_f = fp64 step on a TPU (DESIGN.md §13). `A` is a `dw.DW` pair and
    so are the factors. One `lax.fori_loop` step per column, each a
    masked right-looking rank-1 update with exact products and DW
    subtraction, so the traced program is one column's ops whatever n
    is. The pivot is the largest |hi| of the column below the
    diagonal."""
    n = A.hi.shape[-1]
    rows = jnp.arange(n)
    zero = dw.zeros((n,))

    def step(k, carry):
        A, perm, pivmin = carry
        col = dw.get(A, k, axis=1)
        mag = jnp.where(rows >= k, jnp.abs(col.hi), -jnp.inf)
        p = jnp.argmax(mag)
        rk, rp = dw.get(A, k), dw.get(A, p)
        A = dw.put(dw.put(A, k, rp), p, rk)
        ek, ep = perm[k], perm[p]
        perm = perm.at[k].set(ep).at[p].set(ek)
        col = dw.get(A, k, axis=1)
        pivot = dw.get(col, k)
        pivmin = jnp.minimum(pivmin, jnp.abs(pivot.hi))
        safe = dw.where(pivot.hi == 0, dw.lift(jnp.float32(1)), pivot)
        factors = dw.where(rows > k, dw.div(col, dw.tmap(
            lambda a: jnp.broadcast_to(a, (n,)), safe)), zero)
        rowk = dw.get(A, k)
        prod = dw.mul(dw.tmap(lambda a: a[:, None], factors),
                      dw.tmap(lambda a: a[None, :], rowk))
        upd = (rows[:, None] > k) & (rows[None, :] > k)
        A = dw.where(upd, dw.sub(A, prod), A)
        A = dw.put(A, k, dw.where(rows > k, factors, col), axis=1)
        return A, perm, pivmin

    A1, perm, pivmin = lax.fori_loop(
        0, n, step, (A, rows, jnp.asarray(jnp.inf, jnp.float32)))
    fail = ((pivmin == 0) | ~jnp.all(jnp.isfinite(A1.hi))
            | ~jnp.all(jnp.isfinite(A1.lo)))
    return LUFactors(A1, perm, fail)


def lu_factor_auto(A: jnp.ndarray, fmt_id, backend=None,
                   blocking=None) -> LUFactors:
    """Size-dispatched factorization: blocked panel LU above the policy
    threshold, the strict paper-faithful row loop below (DESIGN.md §6.4).
    The branch is on the static shape, so each size bucket still compiles
    exactly one executable with the format id as runtime data."""
    pol = resolve_blocking(blocking)
    if pol.use_blocked(A.shape[-1]):
        return lu_factor_blocked(A, fmt_id, block=pol.lu_block,
                                 backend=backend)
    return lu_factor(A, fmt_id, backend=backend)
