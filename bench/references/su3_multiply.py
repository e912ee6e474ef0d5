"""Plain reference of SU3_Bench's multiply, on the host in complex128.

C[s, j] = A[s, j] @ B[j] for every site s and link j (the paper's Fig. 1).
A chain of k multiplies with one B is A[s, j] @ B[j]^k; B^k is formed
first (4 matrices), then one product per site.  Imports nothing of the
program.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def chain(a: np.ndarray, b: np.ndarray, k: int = 1) -> np.ndarray:
    """``(S, 4, 3, 3)`` A times ``(4, 3, 3)`` B, k times, in complex128."""
    bk = np.asarray(b, np.complex128)
    b1 = bk
    for _ in range(k - 1):
        bk = np.matmul(bk, b1)
    return np.matmul(np.asarray(a, np.complex128), bk[None])


def bf16(x: np.ndarray) -> np.ndarray:
    """``x`` with real and imaginary parts stored in bfloat16 (as complex64)."""
    out = np.empty(x.shape, np.complex64)
    out.real = np.real(x).astype(ml_dtypes.bfloat16).astype(np.float32)
    out.imag = np.imag(x).astype(ml_dtypes.bfloat16).astype(np.float32)
    return out


def chain_bf16(a: np.ndarray, b: np.ndarray, k: int = 1) -> np.ndarray:
    """The control: the same chain with A, B and every product stored in
    bfloat16 (float32 arithmetic), the precision below the configuration's."""
    c, bq = bf16(a), bf16(b)
    for _ in range(k):
        c = bf16(np.matmul(c, bq[None]))
    return c


def max_abs_err(c: np.ndarray, ref: np.ndarray) -> float:
    """Largest entry-wise distance, over the whole lattice."""
    return float(np.max(np.abs(np.asarray(c, np.complex128) - ref)))
