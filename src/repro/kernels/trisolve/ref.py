"""Pure-jnp oracle for kernels/trisolve.

`_trisolve_core` defines the blocked substitution semantics; the jnp
oracle (`trisolve_ref`, the `JnpBackend.chop_trisolve` implementation)
executes it directly. The Pallas kernel (`trisolve._trisolve_kernel`)
performs the same elementwise ops in the same order on its VMEM-resident
refs, which keeps the two backends bit-identical (DESIGN.md §6.2).

Blocked semantics (DESIGN.md §6.4): for block row i,

  * off-diagonal tiles are chopped matvecs with the strict path's
    product semantics — products rounded to the format, per-tile
    row-sums accumulated *unrounded* in the carrier (a tiled reduction
    over the strict row's prefix sum);
  * one rounding on the off-diagonal subtraction `t = chop(b_i - acc)`;
  * the diagonal block is solved by the strict row loop with the strict
    path's op-level semantics: products rounded, masked carrier row-sum,
    one rounding on the subtraction and (upper) one on the division —
    see `solvers.triangular` for why the division re-rounds.

This module is deliberately pallas-free so the jnp backend never
imports the Pallas toolchain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.precision.chop import chop as _chop_runtime
from repro.precision.chop import tree_sum

# Block sizes are lane-aligned by the default policy (128); the core
# itself only requires n % block == 0 (ops/ref pad via `pad_unit`).


def _trisolve_core(Lu: jnp.ndarray, b2d: jnp.ndarray, chop_fn, *,
                   lower: bool, block: int) -> jnp.ndarray:
    """Blocked forward/backward substitution on the combined LU matrix.

    Lu: (n, n) carrier, n % block == 0. Lower solves read the strictly
    lower triangle with an implicit unit diagonal; upper solves read the
    upper triangle including the diagonal. b2d: (1, n). chop_fn: the
    elementwise round-to-format closure (traced format parameters).
    Returns y: (1, n).
    """
    n = Lu.shape[-1]
    nb = n // block
    Luc = chop_fn(Lu)
    bc = chop_fn(b2d)
    idx = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    rr = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    cc = lax.broadcasted_iota(jnp.int32, (block, block), 1)
    zero = jnp.zeros((), Lu.dtype)

    def blk(bi, y):
        i = bi if lower else nb - 1 - bi
        r0 = i * block

        def off_body(j, acc):
            tile = lax.dynamic_slice(Luc, (r0, j * block), (block, block))
            yj = lax.dynamic_slice(y, (0, j * block), (1, block))
            # Chopped matvec tile, strict-path product semantics:
            # products rounded to the format, carrier row-sum. Rounding
            # the products (an integer-bitcast chain) blocks FMA
            # contraction of the multiply into the row-sum, and the
            # fixed pairwise tree pins the accumulation order, both of
            # which XLA would otherwise pick per program context
            # (DESIGN.md §6.2, §7.3).
            return acc + tree_sum(chop_fn(tile * yj), axis=1)[None, :]

        lo, hi = (0, i) if lower else (i + 1, nb)
        acc = lax.fori_loop(lo, hi, off_body,
                            jnp.zeros((1, block), Lu.dtype))
        rhs = lax.dynamic_slice(bc, (0, r0), (1, block))
        t = chop_fn(rhs - acc)

        diag = lax.dynamic_slice(Luc, (r0, r0), (block, block))
        # Mask to the triangle the solve reads (unit diagonal of a lower
        # solve is implicit and never multiplied).
        tri = jnp.where(rr > cc if lower else rr <= cc, diag, zero)

        def row(rloc, yb):
            r = rloc if lower else block - 1 - rloc
            lrow = lax.dynamic_slice(tri, (r, 0), (1, block))
            prods = chop_fn(lrow * yb)
            mask = (idx < r) if lower else (idx > r)
            s = tree_sum(jnp.where(mask, prods, zero).reshape(-1))
            val = chop_fn(t[0, r] - s)
            if not lower:
                d = tri[r, r]
                safe = jnp.where(d == 0, jnp.ones((), Lu.dtype), d)
                val = chop_fn(val / safe)
            return lax.dynamic_update_slice(yb, val.reshape(1, 1), (0, r))

        yb = lax.fori_loop(0, block, row, jnp.zeros((1, block), Lu.dtype))
        return lax.dynamic_update_slice(y, yb, (0, r0))

    return lax.fori_loop(0, nb, blk, jnp.zeros_like(bc))


def identity_pad(M: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """Zero-extend a square matrix to n_pad with ones on the padded
    diagonal. The single source of the solution-preserving padding
    convention shared by the blocked trisolve (here) and the blocked LU
    (`solvers/lu.lu_factor_blocked`): the identity tail solves/factors
    trivially and never couples back into the leading n x n block."""
    n = M.shape[-1]
    if n_pad == n:
        return M
    Mp = jnp.pad(M, ((0, n_pad - n), (0, n_pad - n)))
    tail = jnp.arange(n, n_pad)
    return Mp.at[tail, tail].set(jnp.ones((), M.dtype))


def pad_unit(Lu: jnp.ndarray, b: jnp.ndarray, n_pad: int):
    """Identity-extend (Lu, b) to n_pad: padded diagonal 1, padded rhs 0.

    Solution preserving — the padded rows solve 1*y = 0 and never couple
    back — and shared by the kernel wrapper and the oracle so both
    backends run the core on identical shapes (the reduction lengths are
    part of the bit-exactness contract, DESIGN.md §6.2).
    """
    n = Lu.shape[-1]
    if n_pad == n:
        return Lu, b
    return identity_pad(Lu, n_pad), jnp.pad(b, (0, n_pad - n))


@functools.partial(jax.jit, static_argnames=("lower", "block"))
def trisolve_ref(Lu: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
                 lower: bool, block: int = 128) -> jnp.ndarray:
    """Bit-exact jnp oracle for the blocked trisolve kernel
    (`ops.trisolve_op`). Works on any float carrier; the Pallas kernel
    itself is f32-only. b: (n,); returns (n,).

    Jitted deliberately: XLA's eager (op-by-op) execution fuses the
    tile multiply into the row-sum differently than a compiled program
    (FMA contraction), which shifts f32 bits for formats whose chop is
    the identity on the carrier. Every solver path runs under jit, so
    the compiled program IS the contract — the oracle pins it."""
    n = Lu.shape[-1]
    n_pad = -(-n // block) * block
    Lp, bp = pad_unit(Lu, b, n_pad)

    def chop_fn(x):
        return _chop_runtime(x, fmt_id)

    out = _trisolve_core(Lp, bp.reshape(1, n_pad), chop_fn,
                         lower=lower, block=block)
    return out[0, :n]
