"""Operations and HBM bytes the algorithms need, per lattice site.

These are the numerators of every roofline share: what the computation
needs, counted from its definition, not what an implementation moves.  A
share computed from them rises when an implementation stops moving extra
bytes, and reads over 100% only when work is left out.

Words are real words of the configuration's storage dtype; one complex
number is two words.
"""
from __future__ import annotations

import dataclasses

from bench.peaks import Peaks, flops_for_dtype

LINKS = 4  # links per site (one per direction)
SU3 = 3
MATRIX_WORDS = SU3 * SU3 * 2  # one complex 3x3 matrix: 18 words
VECTOR_WORDS = SU3 * 2  # one complex colour vector: 6 words
CMAC_FLOPS = 8  # one complex multiply-add: 4 mul + 4 add

WORD_BYTES = {"float32": 4, "bfloat16": 2}

# SU3_Bench's multiply C[s, j] = A[s, j] @ B[j]: 4 links x 27 complex
# multiply-adds x 8 flops = 864 flops per site (the paper's figure).
MULTIPLY_FLOPS_PER_SITE = LINKS * SU3 ** 3 * CMAC_FLOPS
# A read once and C written once: 2 x 4 x 18 = 144 words per site
# (576 bytes in float32).  B (4 matrices) is read once per call, not per site.
MULTIPLY_WORDS_PER_SITE = 2 * LINKS * MATRIX_WORDS
MULTIPLY_WORDS_PER_CALL = LINKS * MATRIX_WORDS

# One iteration of CG on A = sigma I + S, with S the 8-direction stencil
# (S v)(x) = sum_mu U_mu(x) v(x + mu) + U_mu(x)^dagger v(x - mu).
# The textbook iteration has two global reductions, <p, Ap> and <r, r>;
# each ends a pass over the lattice, because the next step needs its sum:
#
#   pass 1: p' = r + beta p, q = sigma p' + S p', partial <p', q>
#           reads U (4 x 18 = 72), r (6), p (6); writes p' (6), q (6)  = 96
#   pass 2: x += alpha p', r -= alpha q, partial <r, r>
#           reads x, p', r, q (24); writes x, r (12)                    = 36
#
# 132 words per site per iteration (528 bytes in float32).  Neighbour values
# of p' come from the same array as its own site: reading it once is the
# floor (an implementation that gathers 8 neighbour copies pays more).
CG_WORDS_PER_SITE = (LINKS * MATRIX_WORDS + 2 * VECTOR_WORDS + 2 * VECTOR_WORDS
                     + 4 * VECTOR_WORDS + 2 * VECTOR_WORDS)
# 8 directions x 9 complex multiply-adds x 8 = 576 for S; sigma p' + S p'
# 12; p' = r + beta p 12; the x and r updates 12 each; two real dots 12 each.
CG_FLOPS_PER_SITE = 2 * LINKS * SU3 * SU3 * CMAC_FLOPS + 12 + 12 + 12 + 12 + 24


@dataclasses.dataclass(frozen=True)
class Work:
    """Flops and HBM bytes one unit of work needs."""

    flops: float
    bytes: float

    def floor_s(self, peaks: Peaks, dtype: str) -> float:
        """The least time the chip could take: the larger of the compute and
        the memory bound."""
        return max(self.flops / flops_for_dtype(peaks, dtype),
                   self.bytes / peaks.hbm_bytes_per_s)


def multiply(L: int, dtype: str = "float32") -> Work:
    """One C = A x B over an L^4 lattice."""
    sites = L ** 4
    wb = WORD_BYTES[dtype]
    return Work(flops=float(MULTIPLY_FLOPS_PER_SITE * sites),
                bytes=float((MULTIPLY_WORDS_PER_SITE * sites
                             + MULTIPLY_WORDS_PER_CALL) * wb))


def cg_iteration(L: int, dtype: str = "float32") -> Work:
    """One CG iteration on A = sigma I + S over an L^4 lattice."""
    sites = L ** 4
    return Work(flops=float(CG_FLOPS_PER_SITE * sites),
                bytes=float(CG_WORDS_PER_SITE * sites * WORD_BYTES[dtype]))
