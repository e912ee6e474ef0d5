"""Plain reference of MILC's even/odd staggered CG, on the host.

D v(x) = sum_mu eta_mu(x) [U_mu(x) v(x + mu) - U_mu(x - mu)^dagger v(x - mu)],
MILC's normalisation (no 1/2), with eta_x = 1, eta_y = (-1)^x,
eta_z = (-1)^(x+y), eta_t = (-1)^(x+y+z), periodic in space and antiperiodic
in t (the hop across the last time slice changes sign).  Sites are t-major
(``((t*L + z)*L + y)*L + x``), mu = 0..3 the x, y, z, t directions, and an
even-site vector ``(L**4 // 2, 3)`` lists the sites with x + y + z + t even in
increasing site order.

Vectors of one parity are held as ``(3, t, z, y, k)`` with the site's
``x = 2k + (t + z + y + parity) % 2``: a y, z or t hop is an ``np.roll``, an
x hop a roll of k on the rows where the neighbour's k differs.  CG solves
(4 m^2 - D_eo D_oe) x_e = b_e, textbook complex CG in complex128 from x = 0,
to a relative residual far below the program's.  Imports nothing of the
program.
"""
from __future__ import annotations

import numpy as np

from bench.references.su3_multiply import bf16


def _row_parity(L: int) -> np.ndarray:
    """(t + z + y) % 2 on the (t, z, y) rows, shaped (L, L, L, 1)."""
    t, z, y = np.meshgrid(*(np.arange(L),) * 3, indexing="ij")
    return ((t + z + y) % 2)[..., None]


def _split(a: np.ndarray, L: int, parity: int) -> np.ndarray:
    """``(..., t, z, y, x)`` -> ``(..., t, z, y, k)``: the sites of one parity."""
    x = (_row_parity(L) + parity) % 2 + 2 * np.arange(L // 2)
    return np.take_along_axis(a, np.broadcast_to(x, a.shape[:-1] + (L // 2,)), axis=-1)


def operator(u: np.ndarray, L: int, dtype=np.complex128) -> dict:
    """The links each hop reads, per output parity, ``(4, 3, 3, t, z, y, k)``:
    ``fwd[P]`` = eta_mu(x) U_mu(x) and ``bwd[P]`` = eta_mu(x) U_mu(x - mu)^dagger
    at the sites x of parity P; the boundary signs stay on the hops."""
    links = np.asarray(u, dtype).reshape(L, L, L, L, 4, 3, 3).transpose(4, 5, 6, 0, 1, 2, 3)
    t, z, y, x = np.meshgrid(*(np.arange(L),) * 4, indexing="ij")
    eta = np.stack([np.ones_like(x), (-1) ** x, (-1) ** (x + y), (-1) ** (x + y + z)])
    fwd = eta[:, None, None] * links
    back = np.stack([np.roll(links[mu], 1, axis=5 - mu) for mu in range(4)])  # U_mu(x - mu)
    bwd = eta[:, None, None] * np.conj(back.transpose(0, 2, 1, 3, 4, 5, 6))
    return {"fwd": [np.ascontiguousarray(_split(fwd, L, p)) for p in (0, 1)],
            "bwd": [np.ascontiguousarray(_split(bwd, L, p)) for p in (0, 1)],
            "row": _row_parity(L),
            "fwd_bc": np.where(np.arange(L) == L - 1, -1.0, 1.0)[:, None, None, None],
            "bwd_bc": np.where(np.arange(L) == 0, -1.0, 1.0)[:, None, None, None]}


def dslash_half(op: dict, w: np.ndarray, parity: int) -> np.ndarray:
    """``D`` at the sites of ``parity`` from ``w (3, t, z, y, k)``, a vector
    of the other parity."""
    # an x hop from row parity r: the forward neighbour sits at k + 1 where
    # (r + parity) is odd, else at k; the backward one at k - 1 where it is
    # even, else at k
    step = (op["row"] + parity) % 2 == 1
    out = np.zeros_like(w)
    for mu in range(4):
        axis = 4 - mu  # axis of direction mu in (colour, t, z, y, k)
        f = np.roll(w, -1, axis=axis)
        b = np.roll(w, 1, axis=axis)
        if mu == 0:
            f = np.where(step, f, w)
            b = np.where(step, w, b)
        if mu == 3:
            f = f * op["fwd_bc"]
            b = b * op["bwd_bc"]
        out += np.einsum("km...,m...->k...", op["fwd"][parity][mu], f)
        out -= np.einsum("km...,m...->k...", op["bwd"][parity][mu], b)
    return out


def solve(u: np.ndarray, b_e: np.ndarray, L: int, mass: float, *, tol: float,
          max_iters: int = 1000, store=None) -> tuple[np.ndarray, int, float]:
    """CG on ``(4 m^2 - D_eo D_oe) x_e = b_e``; returns ``(x_e (S/2, 3),
    iterations, relative residual)``.  ``store`` rounds every stored field
    (the control passes the bfloat16 store)."""
    keep = store or (lambda x: x)
    op = operator(u, L)
    op["fwd"] = [keep(a) for a in op["fwd"]]
    op["bwd"] = [keep(a) for a in op["bwd"]]
    sigma = 4.0 * mass * mass
    half = (L, L, L, L // 2)
    bl = keep(np.asarray(b_e, np.complex128).reshape(*half, 3).transpose(4, 0, 1, 2, 3))
    x = np.zeros_like(bl)
    r = bl.copy()
    p = r.copy()
    rs = b_rs = float(np.vdot(bl, bl).real)
    it, rel = 0, 1.0
    while it < max_iters:
        ap = keep(sigma * p - dslash_half(op, keep(dslash_half(op, p, 1)), 0))
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
    return x.transpose(1, 2, 3, 4, 0).reshape(-1, 3), it, rel


def solve_bf16(u: np.ndarray, b_e: np.ndarray, L: int, mass: float, *,
               tol: float, max_iters: int) -> np.ndarray:
    """The control: the same CG with the links and every vector stored in
    bfloat16, the precision below the configuration's."""
    return solve(u, b_e, L, mass, tol=tol, max_iters=max_iters, store=bf16)[0]


def rel_err(x: np.ndarray, ref: np.ndarray) -> float:
    """Largest entry-wise distance over the largest reference entry."""
    ref = np.asarray(ref, np.complex128)
    return float(np.max(np.abs(np.asarray(x, np.complex128) - ref))
                 / np.max(np.abs(ref)))
