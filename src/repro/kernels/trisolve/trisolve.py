"""Pallas TPU kernel: blocked triangular substitution in emulated precision.

The strict row-loop forward/backward substitutions dominate GMRES-IR/CG-IR
wall time at small-to-medium n: every row is a kernel-launch-sized piece
of work with an HBM round trip on the jnp path. This kernel runs the
*whole* blocked solve — off-diagonal fused chopped-matvec tiles plus the
strict-row-loop diagonal solves — in one launch with the factor matrix
VMEM-resident, mirroring how kernels/qmatmul fuses the matvec.

The kernel performs the same elementwise operations, in the same order,
as the jnp oracle `ref._trisolve_core`, so the two backends agree
bitwise (DESIGN.md §6.2). It expresses them the way Mosaic lowers them:

  * the factor arrives as column blocks, (nb, n, block), so a tile is a
    static ref load and a diagonal-block row a one-row load at a dynamic
    sublane offset;
  * the right-hand side arrives as a column, (n, 1), so the tile
    row-sums (a column) subtract from it without a relayout;
  * the solution is stored as rows, (nb, block), one ref row per block;
  * reading one element of a vector is a masked max over the vector
    with -inf elsewhere (exact for every value, signed zeros and NaN
    included), and writing one is a masked select.

Format parameters live in SMEM as runtime data — one compiled kernel
serves every precision action (DESIGN.md §3.4).

Whole-matrix VMEM residency caps the kernel at moderate n (the ops
wrapper routes larger systems to the oracle); the paper's Table 2/4
grids and the serving buckets sit comfortably below the cap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.chop.chop import block_spec, fmt_spec, ref_chop
from repro.precision.chop import tree_sum

# Above this padded size the solve no longer fits VMEM: the kernel
# holds the whole (n, n) factor (f32: 4 MiB per copy at n = 1024, of a
# 16 MiB default scoped budget); ops.trisolve_op falls back to the
# bit-identical oracle beyond it.
MAX_N = 1024


def _pick(v, mask):
    """The one element of `v` where `mask` holds, as a (1, 1) array."""
    picked = jnp.where(mask, v, np.float32(-np.inf))
    return jnp.max(jnp.max(picked, axis=0, keepdims=True), axis=1,
                   keepdims=True)


def _loop(n: int, body, init):
    """`lax.fori_loop(0, n, body, init)` with an int32 index. fori_loop
    with static bounds counts in a Python int, a 64-bit integer under
    x64, which Mosaic cannot lower."""
    def step(carry):
        i, val = carry
        return i + np.int32(1), body(i, val)
    return lax.while_loop(lambda carry: carry[0] < np.int32(n), step,
                          (np.int32(0), init))[1]


def _trisolve_kernel(fmt_ref, lu_ref, b_ref, y_ref, *, lower: bool,
                     block: int):
    """fmt_ref (SMEM): int32[1, 4]; lu_ref: (nb, n, block) column blocks
    of the combined LU matrix; b_ref: (n, 1); y_ref: (nb, block).

    The block loops are unrolled in Python (nb <= MAX_N // block), so
    every tile load is static; only the row loop inside the diagonal
    block runs on the device."""
    chop = ref_chop(fmt_ref)
    nb = y_ref.shape[0]
    zero = np.float32(0.0)
    lane = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    sub = lax.broadcasted_iota(jnp.int32, (block, 1), 0)

    for bi in range(nb):
        i = bi if lower else nb - 1 - bi
        r0 = i * block
        rows = slice(r0, r0 + block)
        # Chopped matvec tiles, strict-path product semantics: products
        # rounded to the format, carrier row-sum by the fixed pairwise
        # tree (DESIGN.md §6.2, §7.3), accumulated in block order.
        acc = jnp.zeros((block, 1), jnp.float32)
        for j in (range(i) if lower else range(i + 1, nb)):
            tile = chop(lu_ref[j, rows, :])
            acc = acc + tree_sum(chop(tile * y_ref[j:j + 1, :]), axis=1,
                                 keepdims=True)
        t = chop(chop(b_ref[rows, :]) - acc)

        def row(rloc, yb):
            r = rloc if lower else np.int32(block - 1) - rloc
            lrow = chop(lu_ref[i, pl.ds(r0 + r, 1), :])
            prods = chop(lrow * yb)
            mask = (lane < r) if lower else (lane > r)
            s = tree_sum(jnp.where(mask, prods, zero), axis=1,
                         keepdims=True)
            val = chop(_pick(t, sub == r) - s)
            if not lower:
                d = _pick(lrow, lane == r)
                safe = jnp.where(d == zero, np.float32(1.0), d)
                val = chop(val / safe)
            return jnp.where(lane == r, val, yb)

        y_ref[i:i + 1, :] = _loop(block, row,
                                  jnp.zeros((1, block), jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("lower", "block", "interpret"))
def trisolve_pallas(Lu: jnp.ndarray, b2d: jnp.ndarray,
                    fmt_params: jnp.ndarray, *, lower: bool,
                    block: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Lu: (n, n) f32 with n % block == 0 (padded by ops.trisolve_op);
    b2d: (1, n) f32. fmt_params: int32[1, 4]. Returns y as (1, n)."""
    n = Lu.shape[-1]
    assert n % block == 0, "pad to a block multiple (ops.trisolve_op)"
    nb = n // block
    cols = Lu.reshape(n, nb, block).transpose(1, 0, 2)
    y = pl.pallas_call(
        functools.partial(_trisolve_kernel, lower=lower, block=block),
        in_specs=[
            fmt_spec(fmt_params.shape[-1]),
            block_spec((nb, n, block), lambda: (0, 0, 0)),
            block_spec((n, 1), lambda: (0, 0)),
        ],
        out_specs=block_spec((nb, block), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        name="trisolve",
        interpret=interpret,
    )(fmt_params, cols, b2d.reshape(n, 1))
    return y.reshape(1, n)
