"""Plain reference of the CG solve on A = sigma I + S, on the host.

(S v)(x) = sum_mu U_mu(x) v(x + mu) + U_mu(x)^dagger v(x - mu), periodic,
with sites t-major (``((t*L + z)*L + y)*L + x``) and mu = 0..3 the x, y, z, t
directions.  Textbook complex CG in complex128, from x = 0, to a relative
residual far below the program's, so its answer stands for the exact
solution.  Imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench.references.su3_multiply import bf16


def _lattice_links(u: np.ndarray, L: int, dtype=np.complex128) -> np.ndarray:
    """``(S, 4, 3, 3)`` -> ``(4, 3, 3, t, z, y, x)``."""
    return np.ascontiguousarray(
        np.asarray(u, dtype).reshape(L, L, L, L, 4, 3, 3).transpose(4, 5, 6, 0, 1, 2, 3))


def apply(links: np.ndarray, v: np.ndarray, sigma: float) -> np.ndarray:
    """``sigma v + S v`` on lattice-shaped ``links (4,3,3,t,z,y,x)`` and
    ``v (3,t,z,y,x)``."""
    out = sigma * v
    for mu in range(4):
        axis = 4 - mu  # lattice axis of direction mu in (colour, t, z, y, x)
        fwd = np.roll(v, -1, axis=axis)  # v(x + mu)
        bwd = np.roll(v, 1, axis=axis)  # v(x - mu)
        u = links[mu]
        for k in range(3):
            for m in range(3):
                out[k] += u[k, m] * fwd[m] + np.conj(u[m, k]) * bwd[m]
    return out


def solve(u: np.ndarray, b: np.ndarray, L: int, sigma: float, *, tol: float,
          max_iters: int = 200, store=None) -> tuple[np.ndarray, int, float]:
    """CG on ``(sigma I + S) x = b``; returns ``(x (S, 3), iterations,
    relative residual)``.  ``store`` rounds every stored field (the control
    passes the bfloat16 store)."""
    keep = store or (lambda x: x)
    links = keep(_lattice_links(u, L))
    bl = keep(np.asarray(b, np.complex128).reshape(L, L, L, L, 3).transpose(4, 0, 1, 2, 3))
    x = np.zeros_like(bl)
    r = bl.copy()
    p = r.copy()
    rs = b_rs = float(np.vdot(bl, bl).real)
    it, rel = 0, 1.0
    while it < max_iters:
        ap = keep(apply(links, p, sigma))
        alpha = rs / float(np.vdot(p, ap).real)
        x = keep(x + alpha * p)
        r = keep(r - alpha * ap)
        rs_new = float(np.vdot(r, r).real)
        it += 1
        rel = (rs_new / b_rs) ** 0.5
        if rel <= tol:
            break
        p = keep(r + (rs_new / rs) * p)
        rs = rs_new
    return x.transpose(1, 2, 3, 4, 0).reshape(L**4, 3), it, rel


def solve_bf16(u: np.ndarray, b: np.ndarray, L: int, sigma: float, *, tol: float,
               max_iters: int) -> np.ndarray:
    """The control: the same CG with the links and every vector stored in
    bfloat16, the precision below the configuration's."""
    return solve(u, b, L, sigma, tol=tol, max_iters=max_iters, store=bf16)[0]


def rel_err(x: np.ndarray, ref: np.ndarray) -> float:
    """Largest entry-wise distance over the largest reference entry."""
    ref = np.asarray(ref, np.complex128)
    return float(np.max(np.abs(np.asarray(x, np.complex128) - ref))
                 / np.max(np.abs(ref)))
