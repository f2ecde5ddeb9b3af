"""Jitted public wrapper for the trisolve kernel: padding + SMEM params."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.chop.ops import make_fmt_params

from .ref import pad_unit, trisolve_ref
from .trisolve import MAX_N, trisolve_pallas


def trisolve_op(Lu: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
                lower: bool, block: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """Blocked triangular solve on the combined LU matrix, f32 carrier.

    Identity-pads n to the block multiple shared with `ref.trisolve_ref`
    (padded shapes and reduction lengths are part of the bit-exactness
    contract, DESIGN.md §6.2) and runs the single-launch kernel. Systems
    larger than `trisolve.MAX_N` exceed the whole-matrix VMEM budget and
    route to the bit-identical oracle — a pure perf choice, like the
    pallas backend's `chop_min_elems` routing. `interpret=True` runs the
    Pallas interpreter (CPU tests).
    """
    if Lu.dtype != jnp.float32 or b.dtype != jnp.float32:
        raise TypeError("trisolve_op targets the f32 TPU carrier; got "
                        f"{Lu.dtype} x {b.dtype}")
    n = Lu.shape[-1]
    n_pad = -(-n // block) * block
    if n_pad > MAX_N:
        return trisolve_ref(Lu, b, fmt_id, lower=lower, block=block)
    Lp, bp = pad_unit(Lu, b, n_pad)
    out = trisolve_pallas(Lp, bp.reshape(1, n_pad), make_fmt_params(fmt_id),
                          lower=lower, block=block, interpret=interpret)
    return out[0, :n]
