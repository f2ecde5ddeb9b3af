"""Plain numpy arithmetic for the references: rounding to a format's
significand on a carrier, and the accuracy rule answers are held to.

A format is named by its significand bits t (implicit bit included):
bf16 8, tf32 11, fp32 24, fp64 53. The configuration states the carrier the
program runs on (`carrier_t`, 24 for the f32 carrier of the TPU path);
a step in a format wider than the carrier runs at the carrier's width.
"""
import numpy as np
from scipy.linalg import solve_triangular

T_BITS = {"bf16": 8, "tf32": 11, "fp32": 24, "fp64": 53}


def chop(x, t: int) -> np.ndarray:
    """Round float64 values to t significand bits, to nearest, by
    Veltkamp's splitting: with c = (2^(53-t) + 1) x, the high part
    c - (c - x) is x rounded to t bits. Values stay inside the f32
    exponent range here, so neither overflow nor subnormals arise."""
    x = np.asarray(x, np.float64)
    if t >= 53:
        return x
    c = x * (2.0 ** (53 - t) + 1.0)
    return c - (c - x)


def error_bounds(t_u: int, t_r: int, n: int, kappa_inf: float,
                 carrier_t: int = 24):
    """(ferr, nbe) bounds for a converged answer: iterative refinement's
    limiting backward error is the rounding of the stored update (u_w,
    the update format's unit roundoff) plus the residual's accumulated
    rounding, at most n u_r for a length-n dot; the forward error
    multiplies the residual term by kappa_inf(A). Both unit roundoffs
    are floored at the carrier's. The factor 10 covers the carrier
    evaluation of the metrics themselves."""
    floor = 2.0 ** -carrier_t
    u_w = max(2.0 ** -t_u, floor)
    u_r = max(2.0 ** -t_r, floor)
    return 10 * (u_w + n * kappa_inf * u_r), 10 * (u_w + n * u_r)


def lu(A: np.ndarray, t: int, block: int = 64):
    """Right-looking LU with partial pivoting in t bits: each panel of
    `block` columns is factored column by column, its U row block by a
    forward substitution, and the trailing block is updated by one
    product accumulated in float64 and rounded once. Returns (LU, perm,
    fail) with P A = L U, (P A)[i] = A[perm[i]]."""
    a = chop(A, t)
    n = a.shape[0]
    perm = np.arange(n)
    for k0 in range(0, n, block):
        k1 = min(k0 + block, n)
        for k in range(k0, k1):
            p = k + int(np.argmax(np.abs(a[k:, k])))
            if p != k:
                a[[k, p]] = a[[p, k]]
                perm[[k, p]] = perm[[p, k]]
            piv = a[k, k]
            if piv == 0 or not np.isfinite(piv):
                return a, perm, True
            a[k + 1:, k] = chop(a[k + 1:, k] / piv, t)
            a[k + 1:, k + 1:k1] = chop(
                a[k + 1:, k + 1:k1]
                - chop(np.outer(a[k + 1:, k], a[k, k + 1:k1]), t), t)
        if k1 < n:
            a[k0:k1, k1:] = chop(solve_triangular(
                a[k0:k1, k0:k1], a[k0:k1, k1:], lower=True,
                unit_diagonal=True, check_finite=False), t)
            a[k1:, k1:] = chop(
                a[k1:, k1:] - chop(a[k1:, k0:k1] @ a[k0:k1, k1:], t), t)
    return a, perm, not np.all(np.isfinite(a))


def lu_solve(a: np.ndarray, perm: np.ndarray, v: np.ndarray,
             t: int) -> np.ndarray:
    """U \\ (L \\ v[perm]) in t bits: each substitution in float64,
    rounded."""
    y = chop(solve_triangular(a, chop(v, t)[perm], lower=True,
                              unit_diagonal=True, check_finite=False), t)
    return chop(solve_triangular(a, y, lower=False, check_finite=False), t)


def dot(a, b, t: int) -> float:
    """Products rounded to t bits, summed in float64, rounded."""
    return float(chop(np.sum(chop(a * b, t)), t))


def final_metrics(A, b, x, x_true):
    """(ferr, nbe, res_norm) of an answer x, in float64 (paper Eq. 17)."""
    res = np.max(np.abs(b - A @ x))
    normA = np.max(np.sum(np.abs(A), axis=1))
    ferr = np.max(np.abs(x - x_true)) / np.max(np.abs(x_true))
    nbe = res / (normA * np.max(np.abs(x)) + np.max(np.abs(b)))
    ferr = ferr if np.isfinite(ferr) else np.inf
    nbe = nbe if np.isfinite(nbe) else np.inf
    return float(ferr), float(nbe), float(res)
