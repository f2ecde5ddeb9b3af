"""Jitted public wrapper for qmatmul: padding + format-id -> SMEM params."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.chop.ops import make_fmt_params

from .qmatmul import (DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, LANE, QMV_BM,
                      qmatmul_pallas, qmv_pallas)


def _pad_to(x, m0, m1):
    p0 = -x.shape[0] % m0
    p1 = -x.shape[1] % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def qmv_op(a: jnp.ndarray, v: jnp.ndarray, fmt_id, *,
           chop_out: bool = True, bm: int | None = None,
           interpret: bool = False) -> jnp.ndarray:
    """Fused chopped matvec for arbitrary (M, K) x (K,) f32 operands.

    Pads K to the LANE multiple shared with `ref.qmv_ref` (the reduction
    shape is part of the bit-exactness contract, DESIGN.md §6.2) and M to
    the row-block multiple, then runs the single-K-block row-sum kernel.
    """
    if a.dtype != jnp.float32 or v.dtype != jnp.float32:
        raise TypeError("qmv_op targets the f32 TPU carrier; got "
                        f"{a.dtype} x {v.dtype}")
    M, K = a.shape
    bm = min(bm or QMV_BM,
             max(LANE, 1 << int(np.ceil(np.log2(max(M, 1))))))
    Kp = -(-K // LANE) * LANE
    ap = _pad_to(a, bm, LANE)
    vp = jnp.pad(v, (0, Kp - K)).reshape(1, Kp)
    out = qmv_pallas(ap, vp, make_fmt_params(fmt_id, chop_out),
                     bm=bm, interpret=interpret)
    return out[:M]


# Largest lane-padded K the single-K-block qgemm kernel keeps in VMEM
# per tile pair; larger reductions fall back to the bit-identical oracle.
QGEMM_MAX_KP = 512


def qgemm_op(a: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
             chop_out: bool = True, bm: int | None = None,
             bn: int | None = None,
             interpret: bool = False) -> jnp.ndarray:
    """Pinned-contract chopped GEMM for (M, K) x (K, N) f32 operands —
    the `backend.chop_matmul` fast path (DESIGN.md §6.2).

    Pads K to the LANE multiple shared with `ref.qgemm_ref` and runs the
    qmatmul kernel with a SINGLE K block (`bk = Kp`), so the kernel's
    per-tile dot performs the same length-Kp reduction as the oracle's
    full-shape dot; dot reductions are M/N-tile-invariant (measured),
    which is what makes the two backends bit-identical. Reductions
    beyond `QGEMM_MAX_KP` route to the oracle (bit-identical by the same
    contract — a pure VMEM-budget choice).
    """
    if a.dtype != jnp.float32 or b.dtype != jnp.float32:
        raise TypeError("qgemm_op targets the f32 TPU carrier; got "
                        f"{a.dtype} x {b.dtype}")
    M, K = a.shape
    _, N = b.shape
    Kp = -(-K // LANE) * LANE
    if Kp > QGEMM_MAX_KP:
        from .ref import qgemm_ref
        return qgemm_ref(a, b, fmt_id, chop_out=chop_out)
    bm = min(bm or DEFAULT_BM, max(8, 1 << int(np.ceil(np.log2(max(M, 1))))))
    bn = min(bn or DEFAULT_BN, max(128, 1 << int(np.ceil(np.log2(max(N, 1))))))
    ap = _pad_to(a, bm, Kp)
    bp = _pad_to(b, Kp, bn)
    out = qmatmul_pallas(ap, bp, make_fmt_params(fmt_id, chop_out),
                         bm=bm, bn=bn, bk=Kp, interpret=interpret)
    return out[:M, :N]


def qmatmul_op(a: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
               chop_out: bool = True, bm: int | None = None,
               bn: int | None = None, bk: int | None = None,
               interpret: bool = False) -> jnp.ndarray:
    """Mixed-precision-emulated matmul for arbitrary (M,K)x(K,N) f32."""
    M, K = a.shape
    _, N = b.shape
    bm = min(bm or DEFAULT_BM, max(8, 1 << int(np.ceil(np.log2(max(M, 1))))))
    bn = min(bn or DEFAULT_BN, max(128, 1 << int(np.ceil(np.log2(max(N, 1))))))
    bk = min(bk or DEFAULT_BK, max(128, 1 << int(np.ceil(np.log2(max(K, 1))))))
    ap = _pad_to(a.astype(jnp.float32), bm, bk)
    bp = _pad_to(b.astype(jnp.float32), bk, bn)
    out = qmatmul_pallas(ap, bp, make_fmt_params(fmt_id, chop_out),
                         bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]
