"""Dense randsvd systems, MATLAB gallery('randsvd', n, kappa, 2):
A = U diag(sigma) V^T with sigma_1..n-1 = sigma_max and sigma_n =
sigma_max / kappa, U and V from the QR of standard-normal matrices,
b = A x_true with x_true standard normal (arXiv 2601.00728 Eq. 31)."""
import numpy as np


def make(n: int, kappa: float, rng: np.random.Generator, params: dict):
    sigma_max = float(params.get("sigma_max", 1.0))
    u, _ = np.linalg.qr(rng.standard_normal((n, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.full(n, sigma_max)
    s[-1] = sigma_max / kappa
    A = (u * s) @ v.T
    x = rng.standard_normal(n)
    return A, A @ x, x
