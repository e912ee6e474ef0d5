"""Pallas TPU kernels relaying a gauge field between its canonical words and
the planar form, on the device.

A canonical complex64 field (S, 4, 3, 3) is 72 interleaved float32 words per
site, stored site after site.  Viewed as float32 ``(S·72/128, 128)`` those
bytes are lane-dense: no lane padding, and a TPU's (8, 128) tiling of the
array is its row-major byte order, so it crosses to the device as one flat
copy.  These kernels turn that flat view into the planar ``(2, 36, S)`` form
the multiply streams, and back, so the host never transposes a field.

Sixteen sites fill nine 128-lane rows exactly (16 x 72 = 9 x 128); site
``j`` of a group starts ``72·j`` words in, at row ``72·j // 128`` and lane
``72·j % 128``, and may run over into the next row.  One grid step holds
``block`` sites, ``block / 16`` groups:

  pack    for each j: a sublane-strided load of that row of every group (and
          of the next row where site j runs over), a lane select and one lane
          rotation put site j's 72 words in lanes 0..71 of its own row; each
          128 such rows transpose to (128, 128), whose rows 0..71 are the
          words, and stride-2 row loads split them into re and im.
  unpack  the inverse: re/im rows interleave into (128, 128) per 128 sites,
          transpose to one site per row, and each group's nine rows are
          assembled from the rotated sites by lane selects.

Pure data movement: every output word is an input word, bit for bit.

Layout contract (float32):
  flat:   (B, S·9/16, 128)  — site-major canonical words, S % 128 == 0
  planar: (B, 2, 36, S)     — [re|im, link*row*col, site]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORDS = 72  # float32 words of one site's four complex 3x3 links
ROWS = WORDS // 2  # planar rows: complex entries per site
LANE = 128
GROUP = 16  # sites per group of whole rows: 16 x 72 = 9 x 128
GROUP_ROWS = GROUP * WORDS // LANE  # 9
MAX_BLOCK = 2048  # sites per grid step

# (site in group, first row, first lane) of each site of a group
_SPANS = tuple((j,) + divmod(WORDS * j, LANE) for j in range(GROUP))


def flat_rows(n_sites: int) -> int:
    """Rows of 128 words holding ``n_sites`` sites' canonical words."""
    return n_sites * WORDS // LANE


def _block(n_sites: int) -> int:
    if n_sites % LANE:
        raise ValueError(f"relayout needs a multiple of {LANE} sites, got {n_sites}")
    block = MAX_BLOCK
    while n_sites % block:
        block //= 2
    return block


def _planar_from_flat_kernel(x_ref, o_ref, y_ref, z_ref):
    g = x_ref.shape[0] // GROUP_ROWS
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, LANE), 1)
    for j, row, off in _SPANS:
        v = x_ref[pl.ds(row, g, stride=GROUP_ROWS), :]
        if off + WORDS > LANE:  # site j runs over into the next row
            nxt = x_ref[pl.ds(row + 1, g, stride=GROUP_ROWS), :]
            v = jnp.where(lane >= off, v, nxt)
        if off:
            v = pltpu.roll(v, LANE - off, 1)  # lane off -> lane 0
        y_ref[pl.ds(j, g, stride=GROUP), :] = v
    for c in range(y_ref.shape[0] // LANE):  # 128 sites at a time
        sites = pl.ds(c * LANE, LANE)
        z_ref[...] = y_ref[sites, :].T  # row w holds word w of each site
        o_ref[0, :, sites] = z_ref[pl.ds(0, ROWS, stride=2), :]
        o_ref[1, :, sites] = z_ref[pl.ds(1, ROWS, stride=2), :]


def _flat_from_planar_kernel(p_ref, o_ref, y_ref, z_ref):
    for c in range(y_ref.shape[0] // LANE):  # 128 sites at a time
        sites = pl.ds(c * LANE, LANE)
        z_ref[pl.ds(0, ROWS, stride=2), :] = p_ref[0, :, sites]
        z_ref[pl.ds(1, ROWS, stride=2), :] = p_ref[1, :, sites]
        # one site per row, its words in lanes 0..71; the lanes above are
        # never selected below
        y_ref[sites, :] = z_ref[...].T
    g = y_ref.shape[0] // GROUP
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, LANE), 1)
    rows: list[jax.Array | None] = [None] * GROUP_ROWS
    for j, row, off in _SPANS:
        v = y_ref[pl.ds(j, g, stride=GROUP), :]
        if off:
            v = pltpu.roll(v, off, 1)  # lane 0 -> lane off
        end = off + WORDS
        here = (lane >= off) & (lane < min(end, LANE))
        rows[row] = v if rows[row] is None else jnp.where(here, v, rows[row])
        if end > LANE:  # the words that run over open the next row
            rows[row + 1] = v
    for q, v in enumerate(rows):
        o_ref[pl.ds(q, g, stride=GROUP_ROWS), :] = v


def planar_from_flat(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Flat canonical words (B, S·9/16, 128) float32 -> planar (B, 2, 36, S)."""
    bsz, rows, lanes = x.shape
    assert lanes == LANE and x.dtype == jnp.float32, (x.shape, x.dtype)
    n_sites = rows * LANE // WORDS
    block = _block(n_sites)
    return pl.pallas_call(
        _planar_from_flat_kernel,
        grid=(bsz, n_sites // block),
        in_specs=[pl.BlockSpec((None, flat_rows(block), LANE), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((None, 2, ROWS, block), lambda b, i: (b, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bsz, 2, ROWS, n_sites), x.dtype),
        scratch_shapes=[pltpu.VMEM((block, LANE), x.dtype),
                        pltpu.VMEM((LANE, LANE), x.dtype)],
        interpret=interpret,
    )(x)


def flat_from_planar(p: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Planar (B, 2, 36, S) float32 -> flat canonical words (B, S·9/16, 128)."""
    bsz, two, rows, n_sites = p.shape
    assert (two, rows) == (2, ROWS) and p.dtype == jnp.float32, (p.shape, p.dtype)
    block = _block(n_sites)
    return pl.pallas_call(
        _flat_from_planar_kernel,
        grid=(bsz, n_sites // block),
        in_specs=[pl.BlockSpec((None, 2, ROWS, block), lambda b, i: (b, 0, 0, i))],
        out_specs=pl.BlockSpec((None, flat_rows(block), LANE), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, flat_rows(n_sites), LANE), p.dtype),
        scratch_shapes=[pltpu.VMEM((block, LANE), p.dtype),
                        pltpu.VMEM((LANE, LANE), p.dtype)],
        interpret=interpret,
    )(p)
