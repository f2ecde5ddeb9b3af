"""Partition-invariant unrounded carrier math (DESIGN.md §6.2, §7.3).

Almost everything on the solver hot path is rounded through `chop`'s
integer-bitcast chain, which pins its bits in any program context. The
exceptions are the *unrounded* carrier reductions — the GMRES/CG
residual norms and the final Eq. 17 metrics — whose bits were at the
mercy of XLA's lowering: a multiply feeding a reduction may or may not
be FMA-contracted depending on fusion context, and the batched dot
lowers differently when a mesh shard holds a single row (batch-1 dot
!= batched dot on XLA:CPU — measured). That made solver outputs
executor-dependent and was the documented residual caveat of §6.2.

These helpers pin the schedule without changing semantics: the product
is materialized behind a value-preserving integer-bitcast barrier (the
same FMA-barrier trick `_chop_core` relies on, minus the rounding), and
the reduction is a per-row / per-vector sum — invariant to how rows are
tiled across devices (the §6.2 property the fused-matvec contract is
built on). Used by `ir.py`/`cg.py` (final metrics) and
`gmres.py`/`cg.py` (inner residual norms).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.precision import dw, fma_barrier, tree_sum


def carrier_residual(A: jnp.ndarray, b: jnp.ndarray,
                     x: jnp.ndarray) -> jnp.ndarray:
    """b - A x with a pinned row-sum schedule (the Eq. 17 epilogue)."""
    return b - tree_sum(fma_barrier(A * x[None, :]), axis=-1)


def carrier_norm(v: jnp.ndarray) -> jnp.ndarray:
    """||v||_2 with a pinned square-then-sum schedule (replaces
    `jnp.linalg.norm` on the unrounded inner-residual path)."""
    return jnp.sqrt(tree_sum(fma_barrier(v * v)))


def carrier_residual_dw(A, b, x, sA=None):
    """b - A x on the double-word carrier (DESIGN.md §13): exact
    products, the pinned DW tree sum, one DW subtraction. `A`, `b`, `x`
    are `dw.DW` pairs; `sA` is `dw.split(A.hi)` when the caller reuses
    the matrix. Serves the u_r = fp64 residual of every outer iteration
    and the Eq. 17 epilogue, whose nbe would otherwise have an f32
    floor."""
    return dw.sub(b, dw.matvec(A, x, sA))
