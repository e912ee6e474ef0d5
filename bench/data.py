"""Seeded inputs of every cell, made on the host with vectorised numpy.

Copied from the program's own generators (``chip_smoke._random_su3`` and
``autotune._cg_measure_problem``) rather than imported, so the yardstick's
data cannot move with the program.  Canonical arrays stay on the host: a TPU
pads their minor dimensions of 3 and 4 to 128 lanes.
"""
from __future__ import annotations

import numpy as np

SEED_MASK = 2**64 - 1


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator for one use (``stream``) of one ``--seed``;
    any whole number is a valid seed."""
    return np.random.default_rng(np.random.SeedSequence([stream, seed & SEED_MASK]))


def _complex_normal(gen: np.random.Generator, shape: tuple[int, ...]) -> np.ndarray:
    out = np.empty(shape, np.complex64)
    out.real = gen.standard_normal(shape, dtype=np.float32)
    out.imag = gen.standard_normal(shape, dtype=np.float32)
    return out


def random_su3(gen: np.random.Generator, n: int) -> np.ndarray:
    """``n`` random SU(3) matrices ``(n, 3, 3)`` complex64: Gram-Schmidt on
    complex normals, row 2 = conj(row0 x row1), so each is unitary with
    determinant 1."""
    z = _complex_normal(gen, (n, 2, 3))
    r0 = z[:, 0]
    r0 /= np.linalg.norm(r0, axis=-1, keepdims=True)
    r1 = z[:, 1]
    r1 -= np.sum(np.conj(r0) * r1, axis=-1, keepdims=True) * r0
    r1 /= np.linalg.norm(r1, axis=-1, keepdims=True)
    out = np.empty((n, 3, 3), np.complex64)
    out[:, 0], out[:, 1] = r0, r1
    # conj(r0 x r1), written out so no temporary of the whole cross product
    out[:, 2, 0] = np.conj(r0[:, 1] * r1[:, 2] - r0[:, 2] * r1[:, 1])
    out[:, 2, 1] = np.conj(r0[:, 2] * r1[:, 0] - r0[:, 0] * r1[:, 2])
    out[:, 2, 2] = np.conj(r0[:, 0] * r1[:, 1] - r0[:, 1] * r1[:, 0])
    return out


def gauge_field(gen: np.random.Generator, L: int) -> np.ndarray:
    """A random SU(3) gauge field ``(L**4, 4, 3, 3)`` complex64: SU3_Bench's
    A lattice, one independent link per site and direction."""
    return random_su3(gen, L**4 * 4).reshape(L**4, 4, 3, 3)


def links(gen: np.random.Generator) -> np.ndarray:
    """A random SU(3) link set ``(4, 3, 3)`` complex64: the multiply's B."""
    return random_su3(gen, 4)


def axis_constant_field(gen: np.random.Generator, L: int) -> np.ndarray:
    """A gauge field ``(L**4, 4, 3, 3)`` whose link U_mu is constant along its
    own direction mu and random SU(3) across the other three.

    Sites are t-major, ``((t*L + z)*L + y)*L + x``, and direction mu = 0..3
    is x, y, z, t.  On this family the site-local-adjoint stencil is exactly
    Hermitian, so CG on sigma I + S converges."""
    field = np.empty((L, L, L, L, 4, 3, 3), np.complex64)  # (t, z, y, x, mu)
    for mu in range(4):
        axis = 3 - mu  # the lattice axis of direction mu
        shape = [L, L, L, L]
        shape[axis] = 1
        u = random_su3(gen, L**3).reshape(*shape, 3, 3)
        field[:, :, :, :, mu] = u
    return field.reshape(L**4, 4, 3, 3)


def vector_field(gen: np.random.Generator, L: int) -> np.ndarray:
    """A complex-normal colour-vector field ``(L**4, 3)`` complex64."""
    return _complex_normal(gen, (L**4, 3))
