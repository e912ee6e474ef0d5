"""Operations and HBM bytes one even/odd staggered CG iteration needs.

The numerator of ``ks_iter_roofline``, counted from the algorithm's
definition in the units of ``bench/counts.py`` (real words of the storage
dtype) and kept apart from it, so the site-local CG's count stays as it was.

One iteration on (4 m^2 - D_eo D_oe) over the S/2 even sites, with p' the new
search direction.  The two global reductions and the Dslash's dependence on
every neighbour of the intermediate odd vector w split it into three passes
over half lattices:

  pass 1 (odd sites):  w = D_oe (r + beta p); p' = r + beta p written once
        links: fwd and bwd of the odd sites   2 x 72 = 144
        reads r, p (12); writes p' (6), w (6)             = 24
  pass 2 (even sites): q = 4 m^2 p' - D_eo w, partial <p', q>
        links: fwd and bwd of the even sites  2 x 72 = 144
        reads w, p' (12); writes q (6)                    = 18
  pass 3 (even sites): x += alpha p', r -= alpha q, partial <r, r>
        reads x, p', r, q (24); writes x, r (12)          = 36

Every link is read once per half-lattice application and every neighbour
value once (an implementation that gathers 8 copies pays more): 366 words
per even site, 183 per lattice site (732 bytes in float32).  Flops: 576 per
output site of each half application (8 directions x 9 complex
multiply-adds x 8), plus 12 each for p' = r + beta p, the shift, the x and r
updates and the two dots: 2 x 576 + 72 = 1224 per even site.
"""
from __future__ import annotations

from bench.counts import (LINKS, MATRIX_WORDS, SU3, CMAC_FLOPS, VECTOR_WORDS,
                          WORD_BYTES, Work)

HALF_LINK_WORDS = 2 * LINKS * MATRIX_WORDS  # forward and backward links: 144
KS_WORDS_PER_EVEN_SITE = (
    HALF_LINK_WORDS + 4 * VECTOR_WORDS  # pass 1
    + HALF_LINK_WORDS + 3 * VECTOR_WORDS  # pass 2
    + 6 * VECTOR_WORDS)  # pass 3
DSLASH_FLOPS_PER_SITE = 2 * LINKS * SU3 * SU3 * CMAC_FLOPS  # 576
KS_FLOPS_PER_EVEN_SITE = 2 * DSLASH_FLOPS_PER_SITE + 6 * 2 * VECTOR_WORDS


def iteration(L: int, dtype: str = "float32") -> Work:
    """One even/odd CG iteration over an L^4 lattice (L^4 / 2 even sites)."""
    even = L ** 4 // 2
    return Work(flops=float(KS_FLOPS_PER_EVEN_SITE * even),
                bytes=float(KS_WORDS_PER_EVEN_SITE * even * WORD_BYTES[dtype]))
