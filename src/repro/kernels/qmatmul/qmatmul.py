"""Pallas TPU kernel: format-emulated matmul (the paper's "run step i in
precision u_i", fused).

The autotuner's chosen precision is enforced by rounding both operands to
the selected format *inside the MXU tile loop* (VMEM-resident), accumulating
in fp32 — the semantics of real mixed-precision GEMM hardware (bf16 x bf16
-> f32 MXU) generalized to any emulated format, without the two extra HBM
round trips a standalone chop pass would cost.

Grid (M/bm, N/bn, K/bk) with K innermost; fp32 VMEM scratch accumulator;
optional output rounding (for "store in format u" steps).

Format parameters live in SMEM as runtime data: one compiled kernel serves
every precision action (DESIGN.md §3.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chop.chop import block_spec, fmt_spec, ref_chop

from .ref import LANE

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def _qmatmul_kernel(fmt_ref, a_ref, b_ref, o_ref, acc_ref):
    """fmt_ref (SMEM): int32[1, 5] = [[t, emin, xmax_bits, saturate,
    chop_out]]."""
    chop = ref_chop(fmt_ref)

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(chop(a_ref[...]), chop(b_ref[...]),
                            preferred_element_type=jnp.float32,
                            precision=lax.Precision.HIGHEST)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        acc = acc_ref[...]
        o_ref[...] = jnp.where(fmt_ref[0, 4] != 0, chop(acc), acc)


QMV_BM = 256  # rows of A per grid step (multiple of LANE)


def _qmv_kernel(fmt_ref, a_ref, v_ref, o_ref):
    """Fused chopped matvec tile: chop operands in VMEM, multiply, row-sum.

    fmt_ref (SMEM): int32[1, 5] = [[t, emin, xmax_bits, saturate,
    chop_out]]. a_ref: (bm, Kp) tile of A; v_ref: (1, Kp); o_ref: (bm, 1).

    The reduction is the VPU-friendly row-sum over the full (lane-padded)
    K axis in one block — deliberately NOT an MXU dot: a matvec is
    memory-bound, and the single-block row-sum gives the jnp oracle
    (`ref.qmv_ref`) an identical reduction: the product is materialized
    behind the FMA barrier and accumulated by the fixed pairwise tree,
    the exact ops the oracle traces, which is what makes the backend
    dispatch layer bit-exact across implementations and program
    contexts (DESIGN.md §6.2, §7.3). Per-row reductions are invariant
    to tiling over rows, so the grid over M does not perturb results.
    """
    from repro.precision import fma_barrier, tree_sum
    chop = ref_chop(fmt_ref)
    # Carrier accumulation, kept as a (bm, 1) column: the row-sum lands
    # on sublanes, and the column is what the store takes without a
    # relayout.
    out = tree_sum(fma_barrier(chop(a_ref[...]) * chop(v_ref[...])),
                   axis=1, keepdims=True)
    o_ref[...] = jnp.where(fmt_ref[0, 4] != 0, chop(out), out)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def qmv_pallas(a: jnp.ndarray, v: jnp.ndarray, fmt_params: jnp.ndarray,
               *, bm: int = QMV_BM, interpret: bool = False) -> jnp.ndarray:
    """a: (Mp, Kp) f32, v: (1, Kp) f32 — padded by ops.qmv_op so that
    Mp % bm == 0, Kp % LANE == 0, bm % LANE == 0. fmt_params:
    int32[1, 5]. Returns the fused chopped matvec as (Mp,)."""
    M, K = a.shape
    assert M % bm == 0 and K % LANE == 0 and bm % LANE == 0
    out = pl.pallas_call(
        _qmv_kernel,
        grid=(M // bm,),
        in_specs=[
            fmt_spec(fmt_params.shape[-1]),
            block_spec((bm, K), lambda i: (i, 0)),
            block_spec((1, K), lambda i: (0, 0)),
        ],
        out_specs=block_spec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), jnp.float32),
        name="qmv",
        interpret=interpret,
    )(fmt_params, a, v)
    return out.reshape(M)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def qmatmul_pallas(a: jnp.ndarray, b: jnp.ndarray, fmt_params: jnp.ndarray,
                   *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   bk: int = DEFAULT_BK,
                   interpret: bool = False) -> jnp.ndarray:
    """a: (M, K) f32, b: (K, N) f32 — M/N/K padded to block multiples by
    ops.qmatmul_op. fmt_params: int32[1, 5]."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _qmatmul_kernel,
        grid=grid,
        in_specs=[
            fmt_spec(fmt_params.shape[-1]),
            block_spec((bm, bk), lambda i, j, k: (i, k)),
            block_spec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=block_spec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="qmatmul",
        interpret=interpret,
    )(fmt_params, a, b)
