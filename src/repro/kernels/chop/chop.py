"""Pallas TPU kernel: quantize-to-format (the bandit's enforcement op).

Every precision action the autotuner selects is *applied* by rounding tensors
to the chosen format. Done naively (jnp.astype round-trips or the pure-jnp
chop) this costs an extra HBM round trip per tensor; as a Pallas kernel the
rounding happens on VMEM-resident tiles and can be fused into producers /
consumers (see kernels/qmatmul for the fused-matmul version).

The kernel body is the same integer RNE algorithm as
repro.precision.chop._chop_core (bit manipulation only — exact, FTZ/DAZ-
immune, and MXU/VPU-friendly: no transcendental ops). Format parameters are
runtime data living in SMEM, so one compiled kernel serves every format id
(DESIGN.md §3.4: recompile-free bandit exploration).

The format parameters are one (1, P) int32 row. The solvers vmap every
kernel with a per-row format id, which batches that row to (B, 1, P) with
a squeezed leading block dimension; keeping the row 2-D is what leaves
the last two block dimensions equal to the array's, as Mosaic requires
of a windowed block.

Layout: input is flattened and tiled (BLOCK_ROWS, 128) — (8,128)-aligned for
the f32 VPU lane structure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.precision.chop import _chop_core

LANE = 128
BLOCK_ROWS = 256  # (256, 128) f32 tile = 128 KiB/buffer in VMEM


def block_spec(block_shape, index_map, **kwargs) -> pl.BlockSpec:
    """`pl.BlockSpec` whose index map yields int32 block indices: under
    x64 a literal 0 in an index map is a 64-bit integer, which Mosaic
    refuses."""
    def i32_map(*grid):
        return tuple(jnp.asarray(v, jnp.int32) for v in index_map(*grid))
    return pl.BlockSpec(block_shape, i32_map, **kwargs)


def fmt_spec(n_params: int, rows: int = 1) -> pl.BlockSpec:
    """SMEM block of the whole (rows, n_params) array of format rows,
    whatever the grid."""
    return block_spec((rows, n_params), lambda *_: (0, 0),
                      memory_space=pltpu.SMEM)


def ref_chop(fmt_ref, row=0):
    """Round-to-format closure over row `row` (static or traced) of a
    kernel's SMEM format rows [t, emin, xmax_bits (int32 view),
    saturate, ...]. emax is implied by xmax_bits, which is the only
    overflow check needed."""
    t = fmt_ref[row, 0]
    emin = fmt_ref[row, 1]
    xmax_bits = fmt_ref[row, 2].astype(jnp.uint32)
    saturate = fmt_ref[row, 3] != 0

    def chop_fn(x):
        return _chop_core(x, t, emin, 0, xmax_bits, saturate)

    return chop_fn


def _chop_kernel(fmt_ref, x_ref, o_ref):
    o_ref[...] = ref_chop(fmt_ref)(x_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def chop_pallas(x: jnp.ndarray, fmt_params: jnp.ndarray, *,
                block_rows: int = BLOCK_ROWS,
                interpret: bool = False) -> jnp.ndarray:
    """Apply round-to-format to `x` (any shape, f32) on TPU via Pallas.

    fmt_params: int32[1, 4] = [[t, emin, xmax_bits_as_int32, saturate]] —
    runtime data (see ops.make_fmt_params / ops.chop_op for the format-id
    wrapper).
    """
    if x.dtype != jnp.float32:
        raise TypeError("chop_pallas targets the f32 TPU carrier; "
                        f"got {x.dtype}")
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    per_block = block_rows * LANE
    n_pad = -n % per_block
    flat = jnp.pad(flat, (0, n_pad))
    rows = flat.shape[0] // LANE
    x2 = flat.reshape(rows, LANE)

    out = pl.pallas_call(
        _chop_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            fmt_spec(fmt_params.shape[-1]),
            block_spec((block_rows, LANE), lambda i: (i, 0)),  # x tile
        ],
        out_specs=block_spec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        name="chop",
        interpret=interpret,
    )(fmt_params, x2)
    return out.reshape(-1)[:n].reshape(shape)
