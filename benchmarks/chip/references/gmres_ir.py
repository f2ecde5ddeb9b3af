"""Plain reference of GMRES-based iterative refinement (arXiv 2601.00728
Alg. 2) under an action (u_f, u, u_g, u_r), in numpy: the LU in u_f,
left-preconditioned MGS-GMRES in u_g with the Givens least-squares
recurrence, the update in u and the residual in u_r, each operation
rounded to its format on the configuration's carrier. The same stopping
rules as the paper: converged when ||z|| <= max(tau, u) ||x||, stagnated
when ||z_i|| >= stag_tol ||z_{i-1}||, i_max outer and m_max inner
iterations, an inner stop at tol_inner or when the residual stalls
(under 5% reduction past the fifth iteration). x0 = 0.

Returns (status, ferr, nbe, n_outer, n_inner) with status 0 converged,
1 stagnated, 2 maximum iterations, 3 failed."""
import numpy as np

from refmath import chop, dot, final_metrics, lu, lu_solve

CONVERGED, STAGNATED, MAXITER, FAILED = 0, 1, 2, 3


def _gmres(A_g, a, perm, r, t, m_max, tol):
    def op(v):
        return lu_solve(a, perm, chop(A_g @ chop(v, t), t), t)

    n = r.shape[0]
    rhat = lu_solve(a, perm, chop(r, t), t)
    beta = float(np.linalg.norm(rhat))
    if not np.isfinite(beta) or beta == 0:
        return np.zeros(n), 0, True
    V = np.zeros((m_max + 1, n))
    V[0] = chop(rhat / beta, t)
    R = np.zeros((m_max + 1, m_max))
    cs, sn = np.zeros(m_max), np.zeros(m_max)
    g = np.zeros(m_max + 1)
    g[0] = beta
    res_prev, j = np.inf, 0
    while j < m_max:
        w = op(V[j])
        h = np.zeros(m_max + 1)
        for i in range(j + 1):
            h[i] = dot(w, V[i], t)
            w = chop(w - chop(h[i] * V[i], t), t)
        hn = float(np.linalg.norm(w))
        happy = hn <= 1e-30
        V[j + 1] = 0.0 if happy else chop(w / hn, t)
        h[j + 1] = hn
        for i in range(j):
            hi, hi1 = h[i], h[i + 1]
            h[i] = chop(cs[i] * hi + sn[i] * hi1, t)
            h[i + 1] = chop(-sn[i] * hi + cs[i] * hi1, t)
        denom = float(np.hypot(h[j], h[j + 1]))
        d = 1.0 if denom == 0 else denom
        cs[j], sn[j] = h[j] / d, h[j + 1] / d
        h[j], h[j + 1] = chop(denom, t), 0.0
        R[:, j] = h
        gj = g[j]
        g[j], g[j + 1] = chop(cs[j] * gj, t), chop(-sn[j] * gj, t)
        res = abs(g[j + 1])
        fin = np.isfinite(res) and np.all(np.isfinite(h))
        stalled = j >= 4 and res > 0.95 * res_prev
        res_prev, j = res, j + 1
        if happy or res <= tol * beta or stalled or not fin:
            break
    y = np.zeros(m_max)
    for row in range(j - 1, -1, -1):
        s = np.sum(chop(R[row, row + 1:j] * y[row + 1:j], t))
        d = R[row, row] if R[row, row] != 0 else 1.0
        y[row] = chop(chop(g[row] - s, t) / d, t)
    z = chop(np.sum(chop(V[:j] * y[:j, None], t), axis=0), t)
    if not np.all(np.isfinite(z)):
        return np.zeros(n), j, True
    return z, j, False


def solve(A, b, x_true, t, t_u_format, cfg):
    """t: effective bits (u_f, u, u_g, u_r) on the carrier; t_u_format:
    the update format's own bits, which set the stopping tolerance."""
    tf, tu, tg, tr = t
    a, perm, fail = lu(A, tf)
    A_g, A_r, b_r = chop(A, tg), chop(A, tr), chop(b, tr)
    conv_tol = max(cfg["tau"], 2.0 ** -t_u_format)
    x = np.zeros_like(b)
    status, n_outer, n_inner, z_prev = MAXITER, 0, 0, np.inf
    if fail:
        status = FAILED
    else:
        for i in range(cfg["i_max"]):
            r = chop(b_r - chop(A_r @ x, tr), tr)
            z, it, gfail = _gmres(A_g, a, perm, r, tg, cfg["m_max"],
                                  cfg["tol_inner"])
            z = chop(z, tu)
            x_new = chop(x + z, tu)
            znorm, xnorm = np.max(np.abs(z)), np.max(np.abs(x_new))
            n_outer, n_inner = i + 1, n_inner + it
            failed = gfail or not np.all(np.isfinite(x_new))
            converged = znorm <= conv_tol * xnorm
            stagnated = i > 0 and znorm >= cfg["stag_tol"] * z_prev
            if failed:
                status = FAILED
                break
            x, z_prev = x_new, znorm
            if converged:
                status = CONVERGED
                break
            if stagnated:
                status = STAGNATED
                break
    ferr, nbe, _ = final_metrics(A, b, x, x_true)
    return status, ferr, nbe, n_outer, n_inner
