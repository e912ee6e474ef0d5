"""Pallas nearest-neighbor SU(3) stencil kernel: the site-local-adjoint stencil
and, with backward links, the staggered Dslash.

The paper's lesson is that SU3_Bench's ceiling is set by how data moves; the
stencil is the first workload in this repo where data moves *between shards*.
Per site ``x`` the kernel applies the 8-point nearest-neighbor operator

    out(x) = sum_mu [ U_mu(x) . v(x + mu_hat)  +  U_mu(x)^dagger . v(x - mu_hat) ]

over the 4 lattice directions (mu = x, y, z, t), where ``U`` is the site's
gauge-link field (the same planar (2, 36, S) array the multiply kernels
stream) and ``v`` is a color 3-vector field in planar (2, 3, S) form.  With
only ``u`` this is the *site-local-adjoint* stencil: the backward term uses
the site's own adjoint link ``U_mu(x)^dagger`` rather than the neighbor's
``U_mu(x - mu_hat)^dagger``, which keeps gauge-field traffic at ONE streamed
read of U per application (the neighbor-gather cost all lands on the small
vector field — exactly the halo traffic ``distributed.sharding.HaloSpec``
prices).

With a second link operand ``u_bwd`` the backward term reads
``u_bwd_mu(x)^dagger`` instead: the staggered (Kogut-Susskind) Dslash of
arXiv:1411.2087, whose links the plan prepares once per field with the
phases, the antiperiodic t boundary and the backward hop's minus sign folded
in (``u_bwd_mu(x) = -eta_mu(x) U_mu(x - mu_hat)``), so the FMA chain is the
same and nothing branches on a sign per hop.

Kernel formulation (same philosophy as ``su3_matmul``):

  * sites map to VPU lanes; the 8 matrix-vector products per site are fully
    unrolled into real FMA chains over (tile,) vectors — no MXU (K=3 wastes
    the systolic array, and the stencil is bandwidth-bound anyway);
  * the *neighbor gathering* happens OUTSIDE the kernel (the plan layer
    materializes 8 shifted views of v); the kernel streams one
    (8, 2, 3, tile) neighbor block plus one (2, 36, tile) link block
    HBM->VMEM per grid step and keeps them resident while the unrolled
    FMA chain runs — "shifted-neighbor loads kept in VMEM";
  * ``accum_dtype`` upcasts the resident tiles so bf16-storage plans
    accumulate at f32 while streaming 2-byte words (same scheme as the
    multiply kernel).

Layout contract:
  u:     (2, 36, S)    — planar gauge links, [re|im, link*row*col, site]
  v_nbr: (8, 2, 3, S)  — planar neighbor vectors, direction-major
                         (+x, +y, +z, +t, -x, -y, -z, -t)
  u_bwd: (2, 36, S)    — optional backward-hop links, planar like ``u``
  -> out: (2, 3, S)    — planar result vector field

The per-site accumulation order is FIXED (mu-major, then l, forward before
backward), so any site-set decomposition that feeds the same per-site inputs
— full lattice, interior-only, boundary-only — produces bit-identical
outputs.  The overlap-scheduled ``ExecutionPlan.stencil_step`` relies on
this to stay bit-identical to the non-overlapped reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.su3_matmul import COMP_ROWS, _expand_tile

LINKS, SU3 = 4, 3
ROWS = LINKS * SU3 * SU3  # 36 complex link entries per site
NBR_DIRS = 2 * LINKS  # +x +y +z +t -x -y -z -t

# 8 matrix-vector products x 9 complex MACs x 8 flops (4 mul + 4 add): the
# useful-flop figure benchmarks report (the combine adds are the MAC adds).
STENCIL_FLOPS_PER_SITE = NBR_DIRS * SU3 * SU3 * 8

# words streamed per site: U (72) + 8 neighbor vectors (8 x 6) + out (6).
# The halo payload constant (6 words per exchanged vector) lives with the
# pricing rules in distributed.sharding.VECTOR_WORDS_PER_SITE.
STENCIL_WORDS_PER_SITE = 2 * ROWS + NBR_DIRS * 2 * SU3 + 2 * SU3

# two-row compressed gauge: U shrinks 72 -> 48 words; the vector traffic is
# unchanged (v is not a gauge field), so 102 words per site total.
STENCIL_COMP_WORDS_PER_SITE = 2 * COMP_ROWS + NBR_DIRS * 2 * SU3 + 2 * SU3


def _flat(j: int, k: int, l: int) -> int:
    return (j * SU3 + k) * SU3 + l


def _stencil_tile(
    u: jax.Array, v_nbr: jax.Array, u_bwd: jax.Array | None = None
) -> jax.Array:
    """out-tile = sum_mu U_mu . v_fwd[mu] + B_mu^dag . v_bwd[mu], unrolled,
    with B = ``u_bwd`` where given and B = U (site-local adjoint) otherwise.

    u, u_bwd: (2, 36, T) planar link tiles, v_nbr: (8, 2, 3, T) neighbor
    tiles.  Accumulation order is fixed (mu outer, l inner, forward then
    backward per (mu, k, l)) — the bit-identity contract of the module
    docstring.
    """
    ur, ui = u[0], u[1]
    br, bi = (ur, ui) if u_bwd is None else (u_bwd[0], u_bwd[1])
    out_r: list = [None] * SU3
    out_i: list = [None] * SU3
    for mu in range(LINKS):
        vf_r, vf_i = v_nbr[mu, 0], v_nbr[mu, 1]  # (3, T)
        vb_r, vb_i = v_nbr[LINKS + mu, 0], v_nbr[LINKS + mu, 1]
        for k in range(SU3):
            acc_r, acc_i = out_r[k], out_i[k]
            for l in range(SU3):
                f = _flat(mu, k, l)  # U[mu, k, l]
                b = _flat(mu, l, k)  # B[mu, l, k], conjugated for the adjoint
                # forward: U[mu,k,l] * v(x+mu)[l]
                tr = ur[f] * vf_r[l] - ui[f] * vf_i[l]
                ti = ur[f] * vf_i[l] + ui[f] * vf_r[l]
                acc_r = tr if acc_r is None else acc_r + tr
                acc_i = ti if acc_i is None else acc_i + ti
                # backward: conj(B[mu,l,k]) * v(x-mu)[l]
                sr = br[b] * vb_r[l] + bi[b] * vb_i[l]
                si = br[b] * vb_i[l] - bi[b] * vb_r[l]
                acc_r = acc_r + sr
                acc_i = acc_i + si
            out_r[k], out_i[k] = acc_r, acc_i
    return jnp.stack(
        [jnp.stack(out_r, axis=0), jnp.stack(out_i, axis=0)], axis=0
    )


def _su3_stencil_kernel(
    u_ref, v_ref, *refs, accum_dtype: str | None = None, compressed: bool = False
):
    """One grid step: the unrolled 8-direction FMA chain on resident tiles.

    ``refs`` is ``(o_ref,)``, or ``(ub_ref, o_ref)`` when the backward hop
    streams its own (2, 36, tile) link block.

    ``accum_dtype`` widens the VREG working precision exactly as in the
    multiply kernel: tiles upcast once on VMEM load, the chain accumulates
    wide, the out-tile narrows back to storage width on the way out.
    ``compressed`` streams (2, 24, tile) two-row link blocks; unlike the
    multiply, the stencil genuinely needs row 2 (the adjoint term reads link
    COLUMNS), so the reconstruct-on-load cross product is load-bearing here.
    """
    o_ref = refs[-1]
    u = u_ref[...]  # (2, 36 | 24, tile) in VMEM
    v = v_ref[...]  # (8, 2, 3, tile) in VMEM
    ub = refs[0][...] if len(refs) == 2 else None
    if accum_dtype is not None:
        u = u.astype(accum_dtype)
        v = v.astype(accum_dtype)
        if ub is not None:
            ub = ub.astype(accum_dtype)
    if compressed:
        u = _expand_tile(u)  # f32 cross product, shared with su3_matmul
    o_ref[...] = _stencil_tile(u, v, ub).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile", "interpret", "accum_dtype", "compressed")
)
def su3_stencil_planar(
    u: jax.Array,
    v_nbr: jax.Array,
    u_bwd: jax.Array | None = None,
    *,
    tile: int = 512,
    interpret: bool = False,
    accum_dtype: str | None = None,
    compressed: bool = False,
) -> jax.Array:
    """Planar SU(3) nearest-neighbor stencil via pallas_call.

    See the module docstring for the operator and layout contract.  The grid
    walks site tiles; per step one (2, 36, tile) link block — (2, 24, tile)
    for two-row ``compressed`` gauge, reconstructed in-register — and one
    (8, 2, 3, tile) neighbor block stream HBM->VMEM and the fully unrolled
    complex FMA chain produces the (2, 3, tile) out block.  With ``u_bwd``
    a second (2, 36, tile) link block streams per step and the backward hop
    reads it (full rows only: phase-folded links are not SU(3), so row 2
    cannot be rebuilt from rows 0 and 1).
    """
    rows = COMP_ROWS if compressed else ROWS
    assert u.ndim == 3 and u.shape[:2] == (2, rows), (u.shape, compressed)
    n_sites = u.shape[2]
    assert v_nbr.shape == (NBR_DIRS, 2, SU3, n_sites), (v_nbr.shape, n_sites)
    assert n_sites % tile == 0, (n_sites, tile)
    link_spec = pl.BlockSpec((2, rows, tile), lambda i: (0, 0, i))
    in_specs = [link_spec, pl.BlockSpec((NBR_DIRS, 2, SU3, tile), lambda i: (0, 0, 0, i))]
    operands = (u, v_nbr)
    if u_bwd is not None:
        assert not compressed and u_bwd.shape == u.shape, (u_bwd.shape, u.shape)
        in_specs.append(link_spec)
        operands += (u_bwd,)
    return pl.pallas_call(
        functools.partial(
            _su3_stencil_kernel, accum_dtype=accum_dtype, compressed=compressed
        ),
        grid=(n_sites // tile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((2, SU3, tile), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((2, SU3, n_sites), u.dtype),
        interpret=interpret,
    )(*operands)


# -- fused CG iteration kernel (stencil + search-direction axpy) --------------
#
# One conjugate-gradient iteration on the shifted operator A = sigma I + S
# spends most of its bytes re-reading the vector fields: the composed form
# materializes p' = r + beta p (one full read+write pass), gathers p' 's
# neighbors, then runs the stencil.  The fused kernel folds the axpy INTO the
# stencil pallas_call: the gathered neighbor tiles arrive as (r_nbr, p_nbr)
# pairs and the kernel forms p'_nbr = r_nbr + beta p_nbr in VMEM registers,
# so p' is never written to and re-read from HBM as a standalone pass, and
# the shifted apply ap = sigma p' + S(p') lands in the same epilogue.
#
# Bit-identity contract (the fused-vs-composed regression tier): gathering is
# indexing, so gather(r + beta p) == gather(r) + beta gather(p) ELEMENTWISE,
# and at f32 storage every fused expression (the axpy, the fixed-order
# stencil chain, the shift-add) is the same f32 op on the same operands as
# the composed path — the iterates match bit for bit.  Mixed-precision plans
# round at different points (the fused path rounds ap once, the composed
# path rounds S before the shift-add), so only f32 is pinned bitwise.

CG_COEFS = 2  # coefficient block columns: [beta, sigma]

# fused-iteration extra flops per site on top of the 576-flop stencil chain:
# 6 real words per color 3-vector, so each axpy/shift/dot costs 12 flops/site
# (6 mul + 6 add).  Per CG iteration: shift (12), x += alpha p (12),
# r -= alpha ap (12), p = r + beta p (12), <p, Ap> (12), <r, r> (12).
CG_ITER_FLOPS_PER_SITE = STENCIL_FLOPS_PER_SITE + 72


def _su3_cg_fused_kernel(
    u_ref, rn_ref, pn_ref, r_ref, p_ref, c_ref, pnew_ref, s_ref,
    *, accum_dtype: str | None = None, compressed: bool = False,
):
    """One grid step of the fused CG iteration.

    Forms the new search direction p' = r + beta p on the resident center
    AND neighbor tiles, then runs the fixed-order stencil chain on p'_nbr
    and writes S(p') next to p' — the axpy and the operator apply share one
    HBM round trip.  The sigma shift-add deliberately stays OUT of the
    kernel: it runs in the plan's shared jitted epilogue for both the fused
    and composed paths, because an in-kernel ``sigma p' + chain`` gets
    FMA-contracted differently than the composed path's separate shift
    program and breaks the f32 bit-identity contract (observed at ~2 ulp).
    """
    u = u_ref[...]        # (2, 36 | 24, tile)
    r_nbr = rn_ref[...]   # (8, 2, 3, tile)
    p_nbr = pn_ref[...]
    r = r_ref[...]        # (2, 3, tile)
    p = p_ref[...]
    if accum_dtype is not None:
        u = u.astype(accum_dtype)
        r_nbr = r_nbr.astype(accum_dtype)
        p_nbr = p_nbr.astype(accum_dtype)
        r = r.astype(accum_dtype)
        p = p.astype(accum_dtype)
    if compressed:
        u = _expand_tile(u)
    beta = c_ref[0, 0].astype(p.dtype)
    p_new = r + beta * p
    v_nbr = r_nbr + beta * p_nbr  # == gather(p_new): gathers are indexing
    pnew_ref[...] = p_new.astype(pnew_ref.dtype)
    s_ref[...] = _stencil_tile(u, v_nbr).astype(s_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile", "interpret", "accum_dtype", "compressed")
)
def su3_cg_fused_planar(
    u: jax.Array,
    r_nbr: jax.Array,
    p_nbr: jax.Array,
    r_p: jax.Array,
    p_p: jax.Array,
    coefs: jax.Array,
    *,
    tile: int = 512,
    interpret: bool = False,
    accum_dtype: str | None = None,
    compressed: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused CG iteration kernel: ``(p', S(p'))`` in one pass.

    u:            (2, 36 | 24, S) planar gauge links (two-row when compressed)
    r_nbr, p_nbr: (8, 2, 3, S) direction-major shifted neighbors of r and p
    r_p, p_p:     (2, 3, S) planar residual / old search direction
    coefs:        (1, 2) float32 [beta, sigma] — data, not static, so the
                  compiled program serves every iteration of every solve.
                  Only beta is consumed in-kernel; sigma rides along for the
                  plan's shared shift epilogue ``ap = sigma p' + S(p')``,
                  which runs OUTSIDE the kernel so the fused and composed
                  paths round identically (f32 bit-identity contract).
    -> (p_new, s): both (2, 3, S) in the storage dtype.
    """
    rows = COMP_ROWS if compressed else ROWS
    assert u.ndim == 3 and u.shape[:2] == (2, rows), (u.shape, compressed)
    n_sites = u.shape[2]
    assert r_nbr.shape == (NBR_DIRS, 2, SU3, n_sites), (r_nbr.shape, n_sites)
    assert p_nbr.shape == (NBR_DIRS, 2, SU3, n_sites), (p_nbr.shape, n_sites)
    assert r_p.shape == (2, SU3, n_sites), (r_p.shape, n_sites)
    assert p_p.shape == (2, SU3, n_sites), (p_p.shape, n_sites)
    assert coefs.shape == (1, CG_COEFS), coefs.shape
    assert n_sites % tile == 0, (n_sites, tile)
    grid = (n_sites // tile,)
    return pl.pallas_call(
        functools.partial(
            _su3_cg_fused_kernel, accum_dtype=accum_dtype, compressed=compressed
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((2, rows, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((NBR_DIRS, 2, SU3, tile), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((NBR_DIRS, 2, SU3, tile), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((2, SU3, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((2, SU3, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((1, CG_COEFS), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((2, SU3, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((2, SU3, tile), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((2, SU3, n_sites), u.dtype),
            jax.ShapeDtypeStruct((2, SU3, n_sites), u.dtype),
        ],
        interpret=interpret,
    )(u, r_nbr, p_nbr, r_p, p_p, coefs)


def stencil_vmem_bytes(
    tile: int, word_bytes: int = 4, accum_word_bytes: int | None = None
) -> int:
    """Working-set estimate for one stencil grid step (U, 8 neighbor, out
    tiles) — the VMEM bound the autotuner gates stencil candidates on.

    With mixed-precision accumulation the resident tiles live at the wider
    of storage/accumulate width once upcast, so that bounds the set.
    """
    w = max(word_bytes, accum_word_bytes or word_bytes)
    return STENCIL_WORDS_PER_SITE * tile * w


# extra resident words/site of the fused CG grid step over the plain stencil:
# the SECOND gathered neighbor field (p alongside r), the two center vectors,
# and the second output (p' next to S(p'))
CG_EXTRA_WORDS_PER_SITE = NBR_DIRS * 2 * SU3 + 3 * (2 * SU3)


def cg_vmem_bytes(
    tile: int, word_bytes: int = 4, accum_word_bytes: int | None = None
) -> int:
    """Working-set estimate for one fused CG grid step — the stencil tile
    set plus the second gathered field and the extra vector tiles; the VMEM
    bound the autotuner gates CG candidates on."""
    w = max(word_bytes, accum_word_bytes or word_bytes)
    return (STENCIL_WORDS_PER_SITE + CG_EXTRA_WORDS_PER_SITE) * tile * w
