"""Pallas TPU kernel for the SU3_Bench core loop.

TPU-native formulation of the paper's explicit/blocked GEMM (§4, §5.4):

  * A 3x3 complex matrix cannot profitably use the 128x128 MXU (K=3 wastes
    >97% of the systolic array) — and the kernel is bandwidth-bound anyway
    (AI = 1.35 fp32). So *sites* map to VPU lanes and the 3x3x3 complex
    product is fully unrolled into real FMA chains over (tile,) vectors:
    the paper's "explicit GEMM with FMA" in lane-vector form.
  * The paper's PIUMA blocking (2x3 + 1x3 to fit the register file) becomes
    site-tile blocking to fit VMEM: one grid step streams an
    (2, 36, tile) A-block HBM->VMEM, produces the C-block, and streams it
    back. tile is the tunable (kernels.ops.DEFAULT_TILE; swept by the
    autotuner and by tests).
  * B (2, 36) is tiny (288 B fp32); it rides in VMEM across all grid steps —
    the paper's "B stays in cache" plus its "copy B transposed" fix: the
    packing step lays B out so the kernel reads it row-major.

Layout contract (planar SoA, packed by kernels.ops / core.su3.layouts):
  a: (2, 36, S)  — [re|im, link*row*col, site], S % tile == 0
  b: (2, 36)     — [re|im, link*row*col]
  -> c: (2, 36, S)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LINKS, SU3 = 4, 3
ROWS = LINKS * SU3 * SU3  # 36 complex entries per site
COMP_ROWS = LINKS * 2 * SU3  # 24: two-row compressed gauge (12 reals/link)
_UNROLL_MAX = 8  # fused chains up to this K are fully unrolled in-kernel


def _flat(j: int, k: int, l: int) -> int:
    return (j * SU3 + k) * SU3 + l


def _cflat(j: int, k: int, l: int) -> int:
    """Row index in the two-row compressed planar form (k in {0, 1})."""
    return (j * 2 + k) * SU3 + l


# full-form row ids of the stored rows, in compressed row order — the
# store-side "drop row 2" map (mirrors layouts.COMP_ROW_INDICES).
_COMP_TO_FULL = tuple(
    _flat(j, k, l) for j in range(LINKS) for k in range(2) for l in range(SU3)
)


def _expand_tile(a: jax.Array) -> jax.Array:
    """(2, 24, T) two-row tile -> (2, 36, T): reconstruct-on-load.

    Per link, row 2 is the unitarity cross product of the two resident rows,
    ``row2 = conj(row0 x row1)``.  The cross product always runs at f32 —
    even for bf16 storage — then narrows back to the tile's working dtype,
    so narrow-storage plans lose no reconstruction precision beyond the one
    storage rounding they already paid.  The expanded tile feeds the same
    fixed-order FMA bodies as full storage.

    Identity contract: the formula and operand grouping match the codec's
    :func:`repro.core.su3.layouts.reconstruct_third_row` exactly, but LLVM
    may contract mul+add pairs into FMAs differently across compiled
    programs, so reconstructed values agree with the out-of-kernel reference
    to ~1 ulp rather than bitwise.  What IS exact: (a) the multiply's stored
    output — rows 0/1 of C depend only on rows 0/1 of A, so reconstruction
    rounding never reaches them — and (b) any site-set decomposition of the
    SAME compressed kernel (interior/boundary/overlap/depth-2 schedules),
    which is where the repo's bit-identity contracts are load-bearing.
    """
    ar, ai = a[0], a[1]
    rows_r: list = [None] * ROWS
    rows_i: list = [None] * ROWS
    for j in range(LINKS):
        for k in range(2):
            for l in range(SU3):
                rows_r[_flat(j, k, l)] = ar[_cflat(j, k, l)]
                rows_i[_flat(j, k, l)] = ai[_cflat(j, k, l)]
        # row2[l] = conj(r0[l+1]*r1[l+2] - r0[l+2]*r1[l+1])  (indices mod 3)
        for l in range(SU3):
            l1, l2 = (l + 1) % SU3, (l + 2) % SU3
            pr, pi = rows_r, rows_i
            f32 = jnp.float32
            a_r, a_i = pr[_flat(j, 0, l1)].astype(f32), pi[_flat(j, 0, l1)].astype(f32)
            b_r, b_i = pr[_flat(j, 1, l2)].astype(f32), pi[_flat(j, 1, l2)].astype(f32)
            c_r, c_i = pr[_flat(j, 0, l2)].astype(f32), pi[_flat(j, 0, l2)].astype(f32)
            d_r, d_i = pr[_flat(j, 1, l1)].astype(f32), pi[_flat(j, 1, l1)].astype(f32)
            xr = (a_r * b_r - a_i * b_i) - (c_r * d_r - c_i * d_i)
            xi = (a_r * b_i + a_i * b_r) - (c_r * d_i + c_i * d_r)
            rows_r[_flat(j, 2, l)] = xr.astype(ar.dtype)
            rows_i[_flat(j, 2, l)] = (-xi).astype(ar.dtype)  # conjugate
    return jnp.stack(
        [jnp.stack(rows_r, axis=0), jnp.stack(rows_i, axis=0)], axis=0
    )


def _compress_tile(c: jax.Array) -> jax.Array:
    """(2, 36, T) full tile -> (2, 24, T): drop each link's third row.

    The output of a chain of SU(3) multiplies on SU(3) inputs is SU(3), so
    its rows 0/1 determine it; rows 0/1 of C also depend only on rows 0/1 of
    A, so the stored result is exact for ANY input — compression error never
    compounds across chained steps.
    """
    return jnp.stack(
        [jnp.stack([c[p, r] for r in _COMP_TO_FULL], axis=0) for p in range(2)],
        axis=0,
    )


def _mult_tile(a: jax.Array, b: jax.Array) -> jax.Array:
    """C-tile = A-tile (x) B, fully unrolled complex FMAs.

    a: (2, 36, T) planar tile, b: (2, 36) planar B. The shared body of the
    single-step and fused multi-iteration kernels.
    """
    ar, ai = a[0], a[1]
    out_r = [None] * ROWS
    out_i = [None] * ROWS
    for j in range(LINKS):
        for k in range(SU3):
            for m in range(SU3):
                # c[j,k,m] = sum_l a[j,k,l] * b[j,l,m]   (complex)
                cr = None
                ci = None
                for l in range(SU3):
                    arow, brow = _flat(j, k, l), _flat(j, l, m)
                    br = b[0, brow]
                    bi = b[1, brow]
                    if cr is None:
                        cr = ar[arow] * br - ai[arow] * bi
                        ci = ar[arow] * bi + ai[arow] * br
                    else:
                        cr = cr + ar[arow] * br - ai[arow] * bi
                        ci = ci + ar[arow] * bi + ai[arow] * br
                out_r[_flat(j, k, m)] = cr
                out_i[_flat(j, k, m)] = ci
    return jnp.stack([jnp.stack(out_r, axis=0), jnp.stack(out_i, axis=0)], axis=0)


def _su3_kernel(
    a_ref,
    b_ref,
    c_ref,
    *,
    k_iters: int = 1,
    accum_dtype: str | None = None,
    compressed: bool = False,
):
    """One grid step: chain ``k_iters`` multiplies on the resident VMEM tile.

    k_iters=1 is the classic single step C = A (x) B.  k_iters>1 feeds C back
    as the next A *without leaving VMEM*: one HBM read of the A-tile and one
    HBM write of the final C-tile amortize over K multiplies — the per-
    iteration dispatch + HBM roundtrip that dominates at small L disappears.
    The chaining (rather than recomputing the identical product) keeps the
    loop un-DCE-able and matches K sequential engine steps fed back C->A.

    ``accum_dtype`` widens the VREG working precision: the A/B tiles are
    upcast once on VMEM load, every FMA in the chain accumulates at that
    width, and the final C-tile narrows back to the storage dtype on the way
    out.  HBM traffic stays at storage width (the MILC-on-KNL reduced-
    precision-storage scheme: stream bf16, accumulate f32).
    """
    a = a_ref[...]  # (2, 36 | 24, tile) in VMEM
    b = b_ref[...]  # (2, 36)            in VMEM (resident across grid steps)
    if accum_dtype is not None:
        a = a.astype(accum_dtype)
        b = b.astype(accum_dtype)
    if compressed:
        a = _expand_tile(a)  # reconstruct-on-load, f32 cross product
    if k_iters <= _UNROLL_MAX:
        # unrolled chain: one straight-line FMA stream, no loop-carry
        # overhead — the compiler sees the whole K-multiply dataflow
        c = a
        for _ in range(k_iters):
            c = _mult_tile(c, b)
    else:
        c = jax.lax.fori_loop(0, k_iters, lambda _, x: _mult_tile(x, b), a)
    if compressed:
        c = _compress_tile(c)  # store two rows; HBM write stays at 48 words
    c_ref[...] = c.astype(c_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "tile", "k_iters", "interpret", "alias", "accum_dtype", "compressed"
    ),
)
def su3_mult_planar(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: int = 512,
    k_iters: int = 1,
    interpret: bool = False,
    alias: bool = False,
    accum_dtype: str | None = None,
    compressed: bool = False,
) -> jax.Array:
    """Planar-SoA SU3 multiply via pallas_call. See module docstring for layout.

    ``k_iters`` chains K multiplies inside one grid step (fused iteration).
    ``alias`` writes the C-tile into A's buffer (``input_output_aliases``) so
    the fused step is a true in-place update; callers that donate A (the
    engine's fused loop rebinds ``a = step(a, b)``) avoid the defensive copy.
    ``accum_dtype`` upcasts the resident tiles for the FMA chain (e.g. bf16
    storage with float32 accumulation) while streaming storage-width bytes.
    ``compressed`` streams two-row gauge blocks (2, 24, tile): row 2 is
    reconstructed in-register on load and dropped again on store, cutting
    the dominant A/C HBM traffic from 72 to 48 words per site.
    """
    rows = COMP_ROWS if compressed else ROWS
    assert a.ndim == 3 and a.shape[:2] == (2, rows), (a.shape, compressed)
    assert b.shape == (2, ROWS), b.shape
    assert k_iters >= 1, k_iters
    n_sites = a.shape[2]
    assert n_sites % tile == 0, (n_sites, tile)
    grid = (n_sites // tile,)
    return pl.pallas_call(
        functools.partial(
            _su3_kernel, k_iters=k_iters, accum_dtype=accum_dtype,
            compressed=compressed,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((2, rows, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((2, ROWS), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((2, rows, tile), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        input_output_aliases={0: 0} if alias else {},
        interpret=interpret,
    )(a, b)


def vmem_bytes(tile: int, word_bytes: int = 4, accum_word_bytes: int | None = None) -> int:
    """Working-set estimate for one grid step (A, C tiles + B) — the quantity
    the paper bounded by the register file and we bound by VMEM (~16 MiB).

    With mixed-precision accumulation the resident tiles live at the *wider*
    of storage and accumulation width once upcast, so that bounds the set.
    Compressed (two-row) plans stream smaller blocks but expand to the full
    36-row tile in registers, so this full-width figure bounds them too —
    the autotuner's VMEM gate stays conservative without a compression knob.
    """
    w = max(word_bytes, accum_word_bytes or word_bytes)
    return (2 * 2 * ROWS * tile + 2 * ROWS) * w
