"""jit'd public wrappers around the Pallas kernels, with backend dispatch.

On TPU the Pallas path compiles natively; on CPU (this container) it runs in
``interpret=True`` mode, which executes the kernel body with standard JAX ops
— bit-identical control flow, no Mosaic. The dry-run/compile paths of the LM
stack use the pure-jnp reference implementations instead (Pallas does not
lower through the CPU AOT pipeline), selected in models/ by backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.su3 import layouts, registry
from repro.core.su3.layouts import Layout
from repro.kernels import ref as kref
from repro.kernels import su3_matmul, su3_relayout, su3_stencil

DEFAULT_TILE = 512


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


@registry.register_kernel(
    "pallas",
    layouts=(Layout.SOA, Layout.AOSOA),
    backends=("pallas",),
    form=registry.PLANAR,
    supports_fused=True,
    supports_accum=True,
    supports_compressed=True,
)
def su3_mult_planar(
    a_p: jax.Array,
    b_p: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    k_iters: int = 1,
    interpret: bool | None = None,
    alias: bool = False,
    accum_dtype: str | None = None,
    compressed: bool = False,
) -> jax.Array:
    """Planar flattened SoA entry point: a_p (2, 36, S), b_p (2, 36).

    ``k_iters`` chains K multiplies in one dispatch (fused iteration stepping);
    ``alias`` requests in-place C-into-A writes via input_output_aliases;
    ``accum_dtype`` accumulates the FMA chain at a wider precision than the
    streamed storage words (bf16-storage / f32-accumulate serving plans);
    ``compressed`` streams two-row gauge blocks a_p (2, 24, S) with
    in-register third-row reconstruction.
    """
    if interpret is None:
        interpret = _use_interpret()
    return su3_matmul.su3_mult_planar(
        a_p, b_p, tile=tile, k_iters=k_iters, interpret=interpret, alias=alias,
        accum_dtype=accum_dtype, compressed=compressed,
    )


@registry.register_kernel(
    "pallas_stencil",
    layouts=(Layout.SOA, Layout.AOSOA),
    backends=("pallas",),
    form=registry.STENCIL,
    supports_accum=True,
    supports_compressed=True,
)
def su3_stencil_planar(
    u_p: jax.Array,
    v_nbr: jax.Array,
    u_bwd: jax.Array | None = None,
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
    accum_dtype: str | None = None,
    compressed: bool = False,
) -> jax.Array:
    """Planar nearest-neighbor stencil entry: u_p (2, 36, S) links — or
    (2, 24, S) two-row compressed, reconstructed in-register —
    v_nbr (8, 2, 3, S) direction-major shifted neighbor vectors -> (2, 3, S).
    ``u_bwd`` (2, 36, S), where given, is the backward hop's own links (the
    staggered operator's); without it the hop adjoints ``u_p``.
    """
    if interpret is None:
        interpret = _use_interpret()
    return su3_stencil.su3_stencil_planar(
        u_p, v_nbr, u_bwd, tile=tile, interpret=interpret,
        accum_dtype=accum_dtype, compressed=compressed,
    )


@registry.register_kernel(
    "pallas_cg",
    layouts=(Layout.SOA, Layout.AOSOA),
    backends=("pallas",),
    form=registry.STENCIL_AXPY,
    supports_accum=True,
    supports_compressed=True,
)
def su3_cg_fused_planar(
    u_p: jax.Array,
    r_nbr: jax.Array,
    p_nbr: jax.Array,
    r_p: jax.Array,
    p_p: jax.Array,
    coefs: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
    accum_dtype: str | None = None,
    compressed: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused CG iteration entry: u_p (2, 36 | 24, S) links, (r_nbr, p_nbr)
    (8, 2, 3, S) gathered neighbors, (r_p, p_p) (2, 3, S) planar vectors,
    coefs (1, 2) [beta, sigma] -> (p_new, S(p_new)); the sigma shift runs
    in the plan's shared epilogue, not in-kernel."""
    if interpret is None:
        interpret = _use_interpret()
    return su3_stencil.su3_cg_fused_planar(
        u_p, r_nbr, p_nbr, r_p, p_p, coefs, tile=tile, interpret=interpret,
        accum_dtype=accum_dtype, compressed=compressed,
    )


def planar_from_flat(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Canonical float32 words (B, S·9/16, 128) -> planar (B, 2, 36, S), on
    the device (:mod:`repro.kernels.su3_relayout`)."""
    if interpret is None:
        interpret = _use_interpret()
    return su3_relayout.planar_from_flat(x, interpret=interpret)


def flat_from_planar(p: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Planar (B, 2, 36, S) float32 -> canonical words (B, S·9/16, 128), on
    the device (:mod:`repro.kernels.su3_relayout`)."""
    if interpret is None:
        interpret = _use_interpret()
    return su3_relayout.flat_from_planar(p, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def su3_mult(
    a: jax.Array, b: jax.Array, *, tile: int = DEFAULT_TILE, interpret: bool | None = None
) -> jax.Array:
    """Canonical complex entry point matching kernels.ref.su3_mult_ref.

    a: (n_sites, 4, 3, 3) complex, b: (4, 3, 3) complex.
    Packs to planar SoA, pads sites to the tile, runs the kernel, unpacks.
    """
    if interpret is None:
        interpret = _use_interpret()
    n_sites = a.shape[0]
    pad = (-n_sites) % tile
    a_p = layouts.pack_soa(a).reshape(2, su3_matmul.ROWS, n_sites)
    if pad:
        a_p = jnp.pad(a_p, ((0, 0), (0, 0), (0, pad)))
    b_p = layouts.to_planar(b).reshape(2, su3_matmul.ROWS)
    c_p = su3_matmul.su3_mult_planar(a_p, b_p, tile=tile, interpret=interpret)
    c_p = c_p[:, :, :n_sites].reshape(2, layouts.LINKS, layouts.SU3, layouts.SU3, n_sites)
    return layouts.unpack_soa(c_p, a.dtype)


# Re-exported oracles so call sites can do `from repro.kernels import ops` and
# flip between kernel and reference with one name change.
su3_mult_ref = kref.su3_mult_ref
su3_mult_planar_ref = kref.su3_mult_planar_ref
