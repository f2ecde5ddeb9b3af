"""Pure-jnp oracle for kernels/qmatmul: chop inputs, f32-accumulate matmul,
optionally chop the output — identical semantics to the fused kernel."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.precision import chop, fma_barrier, tree_sum

# TPU lane width. Single source of truth for the K padding that both the
# pallas kernel (qmatmul.qmv_pallas via ops.qmv_op) and this oracle
# apply: identical reduction shape is the bit-exactness contract
# (DESIGN.md §6.2). Defined here so the oracle stays pallas-free.
LANE = 128


def qmv_ref(a: jnp.ndarray, v: jnp.ndarray, fmt_id,
            chop_out: bool = True) -> jnp.ndarray:
    """Bit-exact jnp oracle for the fused chopped matvec (`ops.qmv_op`).

    Shares the kernel's reduction shape: K is zero-padded to the LANE
    multiple and reduced with one row-sum in the f32 carrier (per-row
    reductions are tiling-invariant over rows, but NOT over reduction
    length — hence the shared padding; DESIGN.md §6.2). Works on any
    float carrier; the pallas kernel itself is f32-only.
    """
    K = a.shape[-1]
    Kp = -(-K // LANE) * LANE
    ap = jnp.pad(a, ((0, 0), (0, Kp - K)))
    vp = jnp.pad(v, (0, Kp - K))
    ac = chop(ap, fmt_id)
    vc = chop(vp, fmt_id)
    # Carrier accumulation, fully pinned: the product is materialized
    # behind the FMA barrier (no context-dependent mul-into-reduce
    # contraction) and the row-sum is the fixed pairwise tree (no
    # context-dependent accumulation order) — the reduction shape alone
    # does not pin the bits once the surrounding program changes, e.g.
    # in a shard_map body (DESIGN.md §6.2, §7.3). The kernel body
    # executes the same barrier + tree.
    out = tree_sum(fma_barrier(ac * vc[None, :]), axis=1)
    if chop_out:
        out = chop(out, fmt_id)
    return out


def qgemm_ref(a: jnp.ndarray, b: jnp.ndarray, fmt_id,
              chop_out: bool = True,
              chop_inputs: bool = True) -> jnp.ndarray:
    """Bit-exact jnp oracle for the pinned-contract chopped GEMM
    (`ops.qgemm_op` — the `backend.chop_matmul` implementation).

    Contract (DESIGN.md §6.2): K is zero-padded to the LANE multiple and
    reduced by ONE carrier dot. The dot's per-element reduction over K is
    invariant to how M and N are tiled (measured on XLA:CPU, including
    under vmap) but NOT to the reduction length — hence the shared K
    padding, exactly as in `qmv_ref`. The kernel runs the same dot on
    (bm, Kp) x (Kp, bn) tiles, so both backends produce identical bits.
    Works on any float carrier; the pallas kernel itself is f32-only.
    """
    K = a.shape[-1]
    Kp = -(-K // LANE) * LANE
    ap = jnp.pad(a, ((0, 0), (0, Kp - K)))
    bp = jnp.pad(b, ((0, Kp - K), (0, 0)))
    if chop_inputs:
        ap = chop(ap, fmt_id)
        bp = chop(bp, fmt_id)
    out = jnp.dot(ap, bp, preferred_element_type=a.dtype,
                  precision=lax.Precision.HIGHEST)
    if chop_out:
        out = chop(out, fmt_id)
    return out


def qmatmul_ref(a: jnp.ndarray, b: jnp.ndarray, fmt_id,
                chop_out: bool = True) -> jnp.ndarray:
    a32 = chop(a.astype(jnp.float32), fmt_id)
    b32 = chop(b.astype(jnp.float32), fmt_id)
    out = jnp.dot(a32, b32, preferred_element_type=jnp.float32,
                  precision=lax.Precision.HIGHEST)
    if chop_out:
        out = chop(out, fmt_id)
    return out


def qmatmul_ref_blocked(a: jnp.ndarray, b: jnp.ndarray, fmt_id, bk: int,
                        chop_out: bool = True) -> jnp.ndarray:
    """Bit-exact oracle for the kernel's K-blocked accumulation order:
    f32 partial dot per K-block, summed sequentially."""
    K = a.shape[1]
    assert K % bk == 0
    a32 = chop(a.astype(jnp.float32), fmt_id)
    b32 = chop(b.astype(jnp.float32), fmt_id)
    acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
    for k0 in range(0, K, bk):
        acc = acc + jnp.dot(a32[:, k0:k0 + bk], b32[k0:k0 + bk, :],
                            preferred_element_type=jnp.float32,
                            precision=lax.Precision.HIGHEST)
    if chop_out:
        acc = chop(acc, fmt_id)
    return acc
