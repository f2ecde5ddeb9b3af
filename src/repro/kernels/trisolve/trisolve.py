"""Pallas TPU kernel: blocked triangular substitution in emulated precision.

The strict row-loop forward/backward substitutions dominate GMRES-IR/CG-IR
wall time at small-to-medium n: every row is a kernel-launch-sized piece
of work with an HBM round trip on the jnp path. This kernel runs the
*whole* blocked solve — off-diagonal fused chopped-matvec tiles plus the
strict-row-loop diagonal solves — in one launch with the factor matrix
VMEM-resident, mirroring how kernels/qmatmul fuses the matvec.

The kernel performs the same elementwise operations, in the same order,
as the jnp oracle `ref._trisolve_core`, so the two backends agree
bitwise (DESIGN.md §6.2).

Batched layout. The solvers are written for one system and vmapped over
a flush's rows; `ops.trisolve_op` carries a `custom_vmap` rule that
hands every system of a vmapped call to ONE launch of this kernel (an
unbatched call is the same kernel with one system). The systems run
side by side, one per sublane: a step of the dependent row loop works
on (G, block) values — row r of the diagonal block of each of G
systems — so a launch runs n row steps for G systems where one system
per launch ran n for each. Lane reductions, masks, picks and selects
act within a sublane, so every system gets exactly the ops it would
get alone, and its bits do not depend on its batch. Mosaic sees:

  * the factors as column blocks, (B, nb, n, block) — the operand shape
    of the vmapped call (the benchmark's roofline counts it) — so a tile
    is a ref load, dynamic only in the system index;
  * per block row, each system's chopped diagonal tile copied into a
    VMEM scratch by a sublane-strided store, row r of system g at scratch
    row r * Gp + g (Gp: G rounded up to a sublane tile), so a row step
    reads one aligned (G, block) slab. Chop is elementwise: chopping the
    tile once here is bit-identical to chopping each row as it is read;
  * each system's off-diagonal tiles as chopped matvecs under its own
    format, accumulated in block order by the same `tree_sum`; the
    (block, 1) row-sum column is transposed to a row, so t is (G, block)
    with one system per sublane;
  * the right-hand sides and solutions as rows, (B, nb, block);
  * reading one element of each row is a masked max over its lanes with
    -inf elsewhere (exact for every value, signed zeros and NaN
    included), and writing one is a masked select.

Format parameters live in SMEM as runtime data, one row per system —
one compiled kernel serves every precision action (DESIGN.md §3.4). The
row loop rounds with (G, 1) parameter columns built once per launch;
per-system tile work rounds with that system's scalars.

Group size and VMEM. A grid step holds G systems' factors (two buffers
each, as the pipeline allocates them), the diagonal scratch and the
small b, t and y blocks. G is the largest divisor of B that fills at most
one vreg's 8 sublanes and whose factors fit `FACTOR_VMEM_BYTES`; a batch
of more than G systems runs a grid over its B / G groups. At MAX_N = 1024
eight systems' factors take 64 MiB of the v5e's 128 MiB of VMEM, so
every bucket runs eight systems a step; the scoped VMEM limit is raised
to what the step holds. The per-system work loops on the device, so the
kernel's code does not grow with G.

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
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chop.chop import block_spec, fmt_spec, ref_chop
from repro.precision.chop import _chop_core, tree_sum

# Above this padded size the solve no longer fits VMEM: the kernel
# holds each system's whole (n, n) factor (f32: 4 MiB per copy at
# n = 1024); ops.trisolve_op falls back to the bit-identical oracle
# beyond it.
MAX_N = 1024

SUBLANES = 8
# VMEM for one grid step's factors, both pipeline buffers counted.
FACTOR_VMEM_BYTES = 64 * 2**20
# Mosaic's default scoped VMEM limit; a step that holds more raises it.
_DEFAULT_VMEM_LIMIT = 16 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def group_size(batch: int, n_pad: int) -> int:
    """Systems per grid step: the largest divisor of `batch` that is at
    most one vreg of sublanes and whose two f32 factor buffers fit
    `FACTOR_VMEM_BYTES`."""
    fit = max(1, FACTOR_VMEM_BYTES // (2 * 4 * n_pad * n_pad))
    cap = min(SUBLANES, fit)
    return max(g for g in range(1, cap + 1) if batch % g == 0)


def _vmem_limit(g: int, n_pad: int, block: int) -> int:
    """Scoped VMEM one grid step needs: two buffers of each blocked
    operand (factors, b, y) and the scratch, plus headroom."""
    gp = _round_up(g, SUBLANES)
    nb = n_pad // block
    rows = g * _round_up(nb, SUBLANES) * block
    blocks = 2 * 4 * (g * n_pad * n_pad + 2 * rows)
    scratch = 4 * (block * gp + gp) * block
    return max(_DEFAULT_VMEM_LIMIT, blocks + scratch + 4 * 2**20)


def _pick(v, mask):
    """The one element of each row of `v` where `mask` holds, as a
    (rows, 1) column."""
    picked = jnp.where(mask, v, np.float32(-np.inf))
    return jnp.max(picked, axis=1, keepdims=True)


def _loop(n: int, body, init):
    """`lax.fori_loop(0, n, body, init)` with an int32 index. fori_loop
    with static bounds counts in a Python int, a 64-bit integer under
    x64, which Mosaic cannot lower."""
    def step(carry):
        i, val = carry
        return i + np.int32(1), body(i, val)
    return lax.while_loop(lambda carry: carry[0] < np.int32(n), step,
                          (np.int32(0), init))[1]


def _rows_chop(fmt_ref, first, count: int):
    """Round-to-format closure for (count, ...) values whose sublane g
    takes the format of SMEM row first + g: the parameters as (count, 1)
    columns, which `_chop_core` broadcasts along the lanes."""
    sub = lax.broadcasted_iota(jnp.int32, (count, 1), 0)

    def column(k):
        col = jnp.broadcast_to(fmt_ref[first, k], (count, 1))
        for g in range(1, count):
            col = jnp.where(sub == np.int32(g),
                            fmt_ref[first + np.int32(g), k], col)
        return col

    t, emin = column(0), column(1)
    xmax_bits = column(2).astype(jnp.uint32)
    saturate = column(3) != 0

    def chop_fn(x):
        return _chop_core(x, t, emin, 0, xmax_bits, saturate)

    return chop_fn


def _trisolve_kernel(fmt_ref, lu_ref, b_ref, y_ref, diag_ref, t_ref, *,
                     lower: bool, block: int):
    """fmt_ref (SMEM): int32[B, 4], one format row per system; lu_ref:
    (G, nb, n, block) column blocks of G combined LU matrices; b_ref and
    y_ref: (G, nb, block); diag_ref: (block * Gp, block) and t_ref:
    (Gp, block), VMEM scratch.

    The block loop is unrolled in Python (nb <= MAX_N // block), so every
    tile offset is static; the loop over the G systems and the row loop
    inside the diagonal block run on the device."""
    systems, nb = lu_ref.shape[0], lu_ref.shape[1]
    gp = diag_ref.shape[0] // block
    first = pl.program_id(0) * np.int32(systems)
    chop_rows = _rows_chop(fmt_ref, first, systems)
    zero = np.float32(0.0)
    lane = lax.broadcasted_iota(jnp.int32, (1, block), 1)

    for bi in range(nb):
        i = bi if lower else nb - 1 - bi
        rows = slice(i * block, (i + 1) * block)

        def system(g, carry):
            chop = ref_chop(fmt_ref, first + g)
            # Chopped matvec tiles, strict-path product semantics:
            # products rounded to the format, carrier row-sum by the
            # fixed pairwise tree (DESIGN.md §6.2, §7.3), accumulated in
            # block order.
            acc = jnp.zeros((block, 1), jnp.float32)
            for j in (range(i) if lower else range(i + 1, nb)):
                tile = chop(lu_ref[g, j, rows, :])
                acc = acc + tree_sum(chop(tile * y_ref[g, j:j + 1, :]),
                                     axis=1, keepdims=True)
            t_ref[pl.ds(g, 1), :] = chop(chop(b_ref[g, i:i + 1, :]) - acc.T)
            diag_ref[pl.ds(g, block, stride=gp), :] = chop(
                lu_ref[g, i, rows, :])
            return carry

        _loop(systems, system, np.int32(0))
        t = t_ref[0:systems, :]

        def row(rloc, yb):
            r = rloc if lower else np.int32(block - 1) - rloc
            lrow = diag_ref[pl.ds(pl.multiple_of(r * np.int32(gp), SUBLANES),
                                  systems), :]
            prods = chop_rows(lrow * yb)
            mask = (lane < r) if lower else (lane > r)
            s = tree_sum(jnp.where(mask, prods, zero), axis=1,
                         keepdims=True)
            val = chop_rows(_pick(t, lane == r) - s)
            if not lower:
                d = _pick(lrow, lane == r)
                safe = jnp.where(d == zero, np.float32(1.0), d)
                val = chop_rows(val / safe)
            return jnp.where(lane == r, val, yb)

        y_ref[:, i, :] = _loop(block, row,
                               jnp.zeros((systems, block), jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("lower", "block", "interpret"))
def trisolve_pallas(Lu: jnp.ndarray, b: jnp.ndarray,
                    fmt_params: jnp.ndarray, *, lower: bool,
                    block: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Lu: (B, n, n) f32 with n % block == 0 (padded by ops.trisolve_op);
    b: (B, n) f32; fmt_params: int32[B, 4], one format row per system.
    Returns y as (B, n)."""
    batch, n = Lu.shape[0], Lu.shape[-1]
    assert n % block == 0, "pad to a block multiple (ops.trisolve_op)"
    nb = n // block
    per_step = group_size(batch, n)
    gp = _round_up(per_step, SUBLANES)
    cols = Lu.reshape(batch, n, nb, block).transpose(0, 2, 1, 3)
    y = pl.pallas_call(
        functools.partial(_trisolve_kernel, lower=lower, block=block),
        grid=(batch // per_step,),
        in_specs=[
            fmt_spec(fmt_params.shape[-1], rows=batch),
            block_spec((per_step, nb, n, block), lambda k: (k, 0, 0, 0)),
            block_spec((per_step, nb, block), lambda k: (k, 0, 0)),
        ],
        out_specs=block_spec((per_step, nb, block), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, nb, block), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block * gp, block), jnp.float32),
                        pltpu.VMEM((gp, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(per_step, n, block)),
        name="trisolve",
        interpret=interpret,
    )(fmt_params, cols, b.reshape(batch, nb, block))
    return y.reshape(batch, n)
