"""Jitted public wrapper for the chop kernel: format-id -> SMEM params."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.precision.chop import FMT_XMAX_BITS32
from repro.precision.formats import FMT_EMIN, FMT_SATURATE, FMT_T

from .chop import BLOCK_ROWS, chop_pallas

# Packed per-format parameter rows: [t, emin, xmax_bits(int32 view), saturate]
_FMT_PACKED = np.stack([
    FMT_T.astype(np.int32),
    FMT_EMIN.astype(np.int32),
    FMT_XMAX_BITS32.view(np.int32),
    FMT_SATURATE.astype(np.int32),
], axis=1)


def make_fmt_params(fmt_id, chop_out=None) -> jnp.ndarray:
    """int32[1, 4] SMEM parameter row for a (possibly traced) format id;
    with `chop_out` given, a fifth column carries that flag (the fused
    matmul kernels' output-rounding switch)."""
    row = jnp.asarray(_FMT_PACKED)[jnp.asarray(fmt_id, jnp.int32)]
    if chop_out is not None:
        row = jnp.concatenate(
            [row, jnp.asarray([1 if chop_out else 0], jnp.int32)])
    return row.reshape(1, -1)


def chop_op(x: jnp.ndarray, fmt_id, *, block_rows: int = BLOCK_ROWS,
            interpret: bool = False) -> jnp.ndarray:
    """Round `x` (f32) to the format selected by the runtime `fmt_id`.
    `interpret=True` runs the Pallas interpreter (CPU tests)."""
    return chop_pallas(x, make_fmt_params(fmt_id), block_rows=block_rows,
                       interpret=interpret)
