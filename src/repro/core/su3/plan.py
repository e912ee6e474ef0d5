"""ExecutionPlan: the one compiled dispatch path for SU3 work.

The paper's peak numbers come from composing the right *tuple* of
(data layout, kernel formulation, blocking factor, first-touch placement);
getting any element wrong silently costs 2x.  This module makes that tuple a
first-class object instead of re-deriving it ad hoc per call site:

    ┌────────────────────────────────────────────────────────────┐
    │ EngineConfig (L, dtype, layout, variant, tile, placement)  │
    └──────────────────────────┬─────────────────────────────────┘
                               ▼  build_plan() — single construction site
    ┌────────────────────────────────────────────────────────────┐
    │ ExecutionPlan                                              │
    │   codec     LayoutCodec     pack/unpack/planar-view/spec   │
    │   kernel    KernelEntry     unified registry (XLA+Pallas)  │
    │   sharding  NamedSharding   placement-aware out_shardings  │
    │   step      jit(raw_step)   ONE compiled dispatch          │
    │   fused(k)  jit K-chained   one dispatch, K multiplies     │
    └──────────────────────────┬─────────────────────────────────┘
               ┌───────────────┼────────────────────┐
               ▼               ▼                    ▼
        SU3Engine       core.autotune        BatchedLatticeRunner
        (bench loop)    (sweeps + cache)     (B lattices, vmapped)

Everything that used to live in ``SU3Engine._build_step`` / ``_pack`` /
``_unpack`` / ``_unpack_padded`` plus the backend dispatch in
``kernels.ops`` and the candidate enumeration in ``core.autotune`` now flows
through here; benchmarks construct plans (via the thin ``SU3Engine``) rather
than wiring layouts by hand.

Fused multi-iteration stepping
------------------------------
``fused_step(k)`` chains K multiplies (C fed back as A) in ONE dispatch.  On
the Pallas path the chain runs *inside* the kernel grid step on the resident
VMEM tile (``k_iters``), so K iterations cost one HBM read + one HBM write
instead of K of each — the dispatch/HBM-roundtrip overhead that dominates at
small L.  On XLA variants the chain is a ``fori_loop`` under one jit.  This
is a TPU-targeted optimization; in interpret mode on CPU it is merely
no-slower (it still removes K-1 dispatches).

Placement
---------
The three policies reproduce the paper's §4 NUMA/first-touch study:
``sharded`` jits the initializer with sharded out_shardings (every device
first-touches its own shard), ``host_scatter`` materializes on one device and
redistributes (the UPI-storm analog, timed separately), ``replicated`` gives
every device the full lattice.

Multi-host meshes
-----------------
``build_plan`` accepts a :class:`repro.launch.mesh.MeshSpec` (or a concrete
2-D mesh with ``("hosts", "devices")`` axes) in place of the legacy 1-D site
mesh.  The site dimension then shards host-major over BOTH axes (rules in
``repro.distributed.sharding``), so every host owns one contiguous slab of
sites, and:

* ``sharded`` placement materializes each host's slab *on that host* via
  ``jax.make_array_from_callback`` — the fleet-scale form of the paper's
  NUMA-aware object creation (no host ever touches another host's sites);
* ``step`` / ``fused_step`` jit with the same sharding as ``out_shardings``,
  so the K-chained multiply never leaves the devices that hold the shard —
  the chain is device-local end to end (the multiply is site-local; the halo
  model in ``distributed.sharding.halo_spec`` prices what a stencil kernel
  would add).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.su3 import layouts, registry
from repro.core.su3 import variants as _variants  # noqa: F401  (registers XLA kernels)
from repro.core.su3.layouts import Layout, LatticeShape, LayoutCodec
from repro.distributed import sharding as dist_sharding
from repro.kernels import ops as _kops  # registers the Pallas kernels
from repro.launch.mesh import MeshSpec
from repro.chaos.faults import NULL_FAULT_PLAN, corrupt_ghosts
from repro.obs.tracer import NULL_TRACER

PLACEMENTS = ("sharded", "host_scatter", "replicated")


def verify_tolerance(
    dtype: str, accum_dtype: str = "", reconstruct: bool = False
) -> float:
    """THE verification tolerance for a plan's fixed-point checks.

    One rule instead of per-call-site constants, keyed on the full precision
    tuple so a new storage/accumulate/reconstruct combination cannot silently
    inherit a tolerance it never earned:

    * storage rounding dominates: bf16 words quantize at ~2^-8, so any plan
      STORING bf16 verifies at 1e-2 even when it accumulates at f32 (the
      accumulate width fixes the chain, not the stored words);
    * f32 storage verifies at 1e-5 — two-row ``reconstruct`` plans stay at
      the same bound because the in-register cross product is ~1 ulp of
      extra f32 error (documented in ``su3_matmul._expand_tile``), orders of
      magnitude inside it.
    """
    del accum_dtype, reconstruct  # keyed-for-future; today storage decides
    return 1e-2 if dtype == "bfloat16" else 1e-5


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The tunable tuple. One instance == one ExecutionPlan identity."""

    L: int = 16
    dtype: str = "float32"  # real STORAGE word dtype: float32 | bfloat16
    layout: Layout = Layout.SOA
    variant: str = "pallas"  # any name in registry.kernel_names()
    tile: int = 512  # Pallas site-tile (VMEM blocking) / AoSoA lane
    placement: str = "sharded"  # sharded | host_scatter | replicated
    iterations: int = 10
    warmups: int = 2
    accum_dtype: str = ""  # "" = accumulate at dtype; "float32" = bf16-storage plans
    compression: str = "none"  # gauge storage: "none" (18-real) | "two_row" (12-real)

    @property
    def word_bytes(self) -> int:
        return layouts.WORD_BYTES[self.dtype]

    @property
    def is_compressed(self) -> bool:
        return self.compression == layouts.GaugeCompression.TWO_ROW.value

    @property
    def compute_dtype(self) -> str:
        """The dtype the FMA chain runs at (storage dtype unless overridden)."""
        return self.accum_dtype or self.dtype

    @property
    def is_mixed_precision(self) -> bool:
        return bool(self.accum_dtype) and self.accum_dtype != self.dtype

    @property
    def complex_dtype(self) -> Any:
        return jnp.complex64  # planar kernels use cfg.dtype words

    @property
    def shape(self) -> LatticeShape:
        return LatticeShape(self.L)


def make_site_mesh(devices: list[jax.Device] | None = None) -> jax.sharding.Mesh:
    """1-D mesh over all devices; the lattice shards on the 'sites' axis."""
    devices = devices if devices is not None else jax.devices()
    return jax.sharding.Mesh(np.array(devices), ("sites",))


def resolve_mesh(
    mesh: jax.sharding.Mesh | MeshSpec | None,
) -> jax.sharding.Mesh:
    """Normalize a plan's mesh argument to a concrete ``jax.sharding.Mesh``.

    Args:
        mesh: ``None`` (legacy 1-D site mesh over all devices), a concrete
            mesh (used as-is), or a :class:`~repro.launch.mesh.MeshSpec`
            (resolved to its (host, device) mesh).
    """
    if mesh is None:
        return make_site_mesh()
    if isinstance(mesh, MeshSpec):
        return mesh.resolve()
    return mesh


def init_canonical(n_sites: int) -> tuple[jax.Array, jax.Array]:
    """su3_bench's make_lattice/init_link: A entries (1,0), B entries (1/3,0)."""
    a = jnp.full((n_sites, layouts.LINKS, layouts.SU3, layouts.SU3), 1.0 + 0.0j, jnp.complex64)
    b = jnp.full((layouts.LINKS, layouts.SU3, layouts.SU3), (1.0 / 3.0) + 0.0j, jnp.complex64)
    return a, b


# -- per-host first-touch init (multi-host sharded placement) -----------------
#
# The canonical benchmark lattice is uniform, so a shard's physical values can
# be built directly in host memory without ever materializing the global
# array: each host constructs exactly its slab (numpy, host-local — the
# "first touch") and jax assembles the global array from the per-shard
# pieces.  Only AOS carries site-position-dependent words (the metadata
# block), which is offset to global ids so the result is bit-identical to the
# single-host jit initializer.

_SITE_DIM = {Layout.AOS: 0, Layout.SOA: 2, Layout.AOSOA: 0}  # phys site axis


def _uniform_phys_shard(
    codec: LayoutCodec, n_sites: int, site_offset: int
) -> np.ndarray:
    """The packed physical form of ``n_sites`` canonical A=(1,0) sites.

    ``site_offset`` is the shard's global first-site id (AOS metadata words
    carry global ids; the gauge field is position-independent).
    """
    wdt = np.dtype(codec.word_dtype)
    if codec.layout == Layout.AOS:
        out = np.zeros((n_sites, layouts.SITE_WORDS_AOS), np.float32)
        out[:, 0:layouts.GAUGE_WORDS:2] = 1.0  # re words; im words stay 0
        idx = np.arange(site_offset, site_offset + n_sites, dtype=np.float32)
        for col in range(5):  # x, y, z, t, index — pack_aos carries idx in all
            out[:, layouts.GAUGE_WORDS + col] = idx
        out[:, layouts.GAUGE_WORDS + 5] = idx % 2  # parity
        return out.astype(wdt)
    if codec.layout == Layout.SOA:
        # codec.planar_rows: 36, or 24 for two-row compressed gauge — the
        # stored rows of the uniform lattice are all (1, 0) either way
        out = np.zeros((2, codec.planar_rows, n_sites), np.float32)
        out[0] = 1.0  # re plane
        return out.astype(wdt)
    n_tiles = n_sites // codec.tile
    out = np.zeros((n_tiles, 2, codec.planar_rows, codec.tile), np.float32)
    out[:, 0] = 1.0
    return out.astype(wdt)


def first_touch_init(
    codec: LayoutCodec, sharding: NamedSharding, padded_sites: int
) -> jax.Array:
    """Materialize the canonical lattice shard-by-shard, each on its owner.

    Every addressable shard is built host-locally (numpy) and placed on the
    device that owns it — no global array, no cross-host transfer, no
    redistribution.  This is the multi-host analogue of the paper's
    first-touch fix: in a real multi-controller run each process executes the
    callback only for its own shards.

    Args:
        codec: the plan's layout codec (decides the physical form).
        sharding: the plan's lattice NamedSharding (site axis over the mesh).
        padded_sites: global site count, already padded to the mesh.

    Returns:
        The global physical A array, sharded per ``sharding``, bit-identical
        to ``jit(pack ∘ init_canonical, out_shardings=sharding)()``.
    """
    aval = jax.eval_shape(
        codec.pack,
        jax.ShapeDtypeStruct(
            (padded_sites, layouts.LINKS, layouts.SU3, layouts.SU3), jnp.complex64
        ),
    )
    site_dim = _SITE_DIM[codec.layout]
    sites_per_index = codec.tile if codec.layout == Layout.AOSOA else 1

    def build_shard(index: tuple[slice, ...] | None) -> np.ndarray:
        sl = (index or (slice(None),) * len(aval.shape))[site_dim]
        lo = sl.start or 0
        hi = sl.stop if sl.stop is not None else aval.shape[site_dim]
        return _uniform_phys_shard(
            codec, (hi - lo) * sites_per_index, lo * sites_per_index
        )

    return jax.make_array_from_callback(aval.shape, sharding, build_shard)


def site_local(
    fn: Callable[..., Any], mesh: jax.sharding.Mesh, in_specs: Any, out_specs: Any
) -> Callable[..., Any]:
    """``fn`` run once per shard of ``mesh`` (``jax.shard_map``).

    Mosaic kernels cannot be partitioned automatically: a ``pallas_call`` on
    a sharded operand must run inside a shard_map.  Every SU3 kernel is
    site-local, so the per-shard call computes exactly the sites that shard
    holds.  Gathers that cross shards stay outside, where XLA partitions them.
    """
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_raw_step(
    codec: LayoutCodec,
    kernel: registry.KernelEntry,
    *,
    tile: int,
    k_iters: int = 1,
    interpret: bool | None = None,
    alias: bool = False,
    mesh: jax.sharding.Mesh | None = None,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Unjitted physical step (a_phys, b_planar) -> c_phys for any kernel form.

    The one place the kernel-form dispatch happens; ExecutionPlan jits this
    and core.autotune lowers it for HLO-level byte accounting.  The codec's
    ``accum_dtype`` (mixed-precision storage plans) flows to planar kernels
    that own their upcast; canonical kernels accumulate in float32 by
    construction (the codec unpacks to complex64).

    With ``mesh``, a planar kernel runs per shard of the lattice's site
    sharding (:func:`site_local`); without it the step is one unsharded call
    (per-lattice bodies that a caller vmaps and shards itself).
    """
    if not kernel.supports_layout(codec.layout):
        raise ValueError(
            f"kernel {kernel.name!r} does not support layout {codec.layout.value!r} "
            f"(supported: {[l.value for l in kernel.layouts]})"
        )
    if kernel.form == registry.STENCIL:
        raise ValueError(
            f"kernel {kernel.name!r} is a nearest-neighbor stencil; it "
            f"dispatches through ExecutionPlan.stencil_step, not a multiply step"
        )
    if kernel.form == registry.STENCIL_AXPY:
        raise ValueError(
            f"kernel {kernel.name!r} is a fused CG iteration body; it "
            f"dispatches through ExecutionPlan.cg_solve, not a multiply step"
        )
    if k_iters > 1 and kernel.form == registry.PLANAR and not kernel.supports_fused:
        raise ValueError(f"kernel {kernel.name!r} does not support fused iteration")
    if codec.is_mixed_precision and not kernel.supports_accum_dtype():
        raise ValueError(
            f"kernel {kernel.name!r} cannot accumulate at {codec.accum_dtype!r} "
            f"over {codec.dtype!r} storage (no accum_dtype support)"
        )
    if codec.is_compressed and not kernel.supports_compression():
        raise ValueError(
            f"kernel {kernel.name!r} cannot stream two-row compressed gauge "
            f"(no reconstruct-on-load path)"
        )

    if kernel.form == registry.PLANAR:
        if not codec.supports_planar_view:
            raise ValueError(
                f"planar kernel {kernel.name!r} needs a planar-view layout, "
                f"got {codec.layout.value!r}"
            )

        def raw_step(a_phys: jax.Array, b_p: jax.Array) -> jax.Array:
            a_p = codec.planar_view(a_phys)
            kw: dict[str, Any] = {"tile": tile, "k_iters": k_iters, "alias": alias}
            if codec.is_mixed_precision:
                kw["accum_dtype"] = codec.accum_dtype
            if codec.is_compressed:
                kw["compressed"] = True
            if interpret is not None:
                kw["interpret"] = interpret
            c_p = kernel.fn(a_p, b_p, **kw)
            return codec.from_planar_view(c_p, a_phys)

        if mesh is not None:
            spec = dist_sharding.lattice_site_spec(codec, mesh)
            raw_step = site_local(raw_step, mesh, (spec, P()), spec)

    else:  # canonical complex kernel wrapped by the codec

        def raw_step(a_phys: jax.Array, b_p: jax.Array) -> jax.Array:
            b = codec.unpack_b(b_p)
            if k_iters == 1:
                return codec.pack(kernel.fn(codec.unpack(a_phys), b))

            def body(_: jax.Array, phys: jax.Array) -> jax.Array:
                return codec.pack(kernel.fn(codec.unpack(phys), b))

            return jax.lax.fori_loop(0, k_iters, body, a_phys)

    return raw_step


STENCIL_VARIANT = "pallas_stencil"
CG_VARIANT = "pallas_cg"

# Default SPD shift of the CG operator A = CG_SHIFT I + S.  Each of the 8
# stencil terms applies one unitary SU(3) row, so ||S|| <= 8; sigma = 16
# keeps the symmetric part positive definite with condition number <= 3
# ((16 + 8) / (16 - 8)), which is what makes the solver a *short*-chain
# serving workload (O(10) iterations to 1e-6) rather than a batch job.
# Note the simplified site-local-adjoint stencil is Hermitian exactly when
# every U_mu is constant along its own direction mu (e.g. uniform or
# per-direction-constant SU(3) fields) — the family the convergence tier
# pins; on general fields A is only near-symmetric and CG is best-effort.
CG_SHIFT = 16.0


# -- stencil neighbor geometry ------------------------------------------------
#
# Site linearization is t-major: site = ((t*L + z)*L + y)*L + x, so the host
# slabs of the lattice sharding are contiguous t-slices and the +-t neighbor
# of site s is (s +- L^3) mod L^4 — the only directions whose access crosses
# slab boundaries.  x/y/z neighbor moves permute sites WITHIN one t-slice and
# therefore never leave a (non-degenerate) slab.


def stencil_neighbor_tables(
    L: int, padded_sites: int, n_shards: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Neighbor index tables for the 8-direction stencil.

    Returns ``(global_idx, local_idx, boundary_idx)``:

    * ``global_idx (8, padded_sites)`` — exact periodic neighbors, direction
      order (+x, +y, +z, +t, -x, -y, -z, -t).  Padding sites (>= L^4) point
      at themselves: their outputs are garbage and are sliced off at unpack.
    * ``local_idx (8, padded_sites)`` — identical except the +-t directions
      wrap WITHIN each of the ``n_shards`` contiguous slabs, so a gather
      through it moves no data between slabs.  It agrees with ``global_idx``
      exactly on every interior site (``HaloSpec.interior_ranges``) — the
      property the overlap schedule's bit-identity rests on.
    * ``boundary_idx (B,)`` — concatenated ``HaloSpec.boundary_ranges`` of
      every shard (empty on one shard): the sites whose +-t neighbors are
      remote, recomputed by the boundary pass after the exchange lands.
    """
    S = L**4
    if n_shards > 1 and S % n_shards:
        raise ValueError(f"L={L} lattice does not shard over {n_shards} slabs")
    idx = np.arange(S, dtype=np.int64)
    pad_id = np.arange(padded_sites, dtype=np.int64)
    glob = np.tile(pad_id, (8, 1))
    for d in range(4):
        stride = L**d
        c = (idx // stride) % L
        glob[d, :S] = idx + (((c + 1) % L) - c) * stride
        glob[4 + d, :S] = idx + (((c - 1) % L) - c) * stride
    local = glob.copy()
    face = L**3
    if n_shards > 1:
        per = S // n_shards
        base = (idx // per) * per
        off = idx - base
        local[3, :S] = base + (off + face) % per
        local[7, :S] = base + (off - face) % per
    spec = dist_sharding.HaloSpec(L=L, n_shards=n_shards)
    ranges = [
        np.arange(a, b, dtype=np.int64)
        for s in range(n_shards)
        for (a, b) in spec.boundary_ranges(s)
    ]
    bidx = np.concatenate(ranges) if ranges else np.empty(0, np.int64)
    return glob.astype(np.int32), local.astype(np.int32), bidx.astype(np.int32)


def gather_neighbors(v_p: jax.Array, idx: np.ndarray) -> jax.Array:
    """Planar ``(2, 3, S)`` field -> ``(D, 2, 3, N)`` neighbor fields through
    the ``(D, N)`` index table ``idx``.

    One gather per direction: a single ``(D, N)``-indexed gather is the same
    data movement, but compiled alone at L=32 (D=8, N=32**4) for a described
    v5e with jax 0.9.0 / libtpu 0.0.34, it took 29.2 s, a 54.2 MB program and
    201 MB of temp, against 1.5 s, 35.1 MB and no temp for the per-direction
    form (``jit(f).lower(v).compile()`` and its ``memory_analysis()``).
    """
    nbrs = jnp.stack([v_p[:, :, idx[d]] for d in range(idx.shape[0])])
    # materialized, as the kernel operand it is on the chip: under CPU
    # interpret mode XLA would otherwise fuse the gather into the kernel body
    # differently per program, and the fused-vs-composed CG bit-identity
    # contract would hinge on that fusion choice
    return jax.lax.optimization_barrier(nbrs)


# -- neighbours by rolls of the flat site axis ---------------------------------
#
# Every periodic neighbour is a fixed shift of the t-major site axis: +-mu is
# a roll by L**mu, by (L - 1) L**mu the other way on the face where the
# coordinate wraps.  Built from rolls and selects on the flat axis, so the
# site axis stays the lane axis on a TPU, and the face masks come from an
# iota inside the program: no per-site table or mask is an HLO constant.


def _sites(n: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (n,), 0)


def _coord(s: jax.Array, stride: int, L: int) -> jax.Array:
    """``s // stride % L`` for sites ``s >= 0``."""
    return jax.lax.rem(jax.lax.div(s, stride), L)


def _hop(a: jax.Array, s: jax.Array, L: int, stride: int, sign: int) -> jax.Array:
    """``a`` at ``s + sign * stride`` on the site axis, periodic in the
    coordinate ``s // stride % L``: a roll of the last axis, and on the face
    where the coordinate wraps a roll by ``(L - 1) * stride`` the other way."""
    face = _coord(s, stride, L) == (L - 1 if sign > 0 else 0)
    return jnp.where(face, jnp.roll(a, sign * (L - 1) * stride, axis=-1),
                     jnp.roll(a, -sign * stride, axis=-1))


def _odd_rows(row: jax.Array, L: int) -> jax.Array:
    """Whether the row of fixed ``(t, z, y)`` numbered ``row`` has
    ``t + z + y`` odd: its sites of parity p start at x = 1 - p."""
    tzy = _coord(row, 1, L) + _coord(row, L, L) + _coord(row, L**2, L)
    return jax.lax.rem(tzy, 2) == 1


def periodic_neighbors(v_p: jax.Array, L: int) -> jax.Array:
    """Planar ``(2, 3, N)`` field, ``N >= L**4`` -> the ``(8, 2, 3, N)``
    operand :func:`gather_neighbors` reads through the exact periodic table
    of :func:`stencil_neighbor_tables`, value for value, built from rolls:
    direction order (+x, +y, +z, +t, -x, -y, -z, -t), and padding sites
    (``>= L**4``) reading themselves."""
    n = v_p.shape[-1]
    s = _sites(n)
    hops = [_hop(v_p, s, L, L**mu, sign) for sign in (1, -1) for mu in range(4)]
    nbrs = jnp.stack(hops)
    if n > L**4:
        nbrs = jnp.where(s >= L**4, v_p, nbrs)
    return jax.lax.optimization_barrier(nbrs)


def half_neighbors(v_p: jax.Array, L: int, parity: int) -> jax.Array:
    """The other parity's planar half vector ``(2, 3, H)``, ``H >= L**4 // 2``
    -> the ``(8, 2, 3, H)`` operand of the parity-``parity`` sites: what a
    gather through ``staggered_neighbor_tables(L, H)[parity]`` reads, value
    for value, built from rolls.

    A site of either parity at half position h is site 2h or 2h + 1, so
    +-y/z/t are rolls by ``L**mu // 2`` with the full lattice's face
    correction; +-x stays put or moves one position, as the row's parity
    says whether the site's x is odd, and wraps by ``L // 2 - 1`` at x = 0
    and x = L - 1.  Padding sites read themselves."""
    n, half = v_p.shape[-1], L // 2
    h = _sites(n)
    xh = jax.lax.rem(h, half)
    x_odd = _odd_rows(jax.lax.div(h, half), L) != (parity == 1)
    plus_x = jnp.where(x_odd, jnp.where(xh == half - 1,
                                        jnp.roll(v_p, half - 1, axis=-1),
                                        jnp.roll(v_p, -1, axis=-1)), v_p)
    minus_x = jnp.where(x_odd, v_p, jnp.where(xh == 0,
                                              jnp.roll(v_p, 1 - half, axis=-1),
                                              jnp.roll(v_p, 1, axis=-1)))
    plus = [plus_x] + [_hop(v_p, h, L, L**mu // 2, 1) for mu in range(1, 4)]
    minus = [minus_x] + [_hop(v_p, h, L, L**mu // 2, -1) for mu in range(1, 4)]
    nbrs = jnp.stack(plus + minus)
    if n > L**4 // 2:
        nbrs = jnp.where(h >= L**4 // 2, v_p, nbrs)
    return jax.lax.optimization_barrier(nbrs)


def init_stencil_canonical(n_sites: int) -> tuple[jax.Array, jax.Array]:
    """Canonical stencil benchmark data: U entries (1, 0), v entries (1/24, 0).

    With uniform inputs every output component is sum over 8 directions of
    3 entries x 1/24 = exactly (1, 0) — the stencil analogue of su3_bench's
    A=(1,0)/B=(1/3,0) fixed-point check, used by ``verify_stencil``.
    """
    a, _ = init_canonical(n_sites)
    v = jnp.full((n_sites, layouts.SU3), (1.0 / 24.0) + 0.0j, jnp.complex64)
    return a, v


# divergence guard: rs blowing past this multiple of ||b||^2 is treated as
# breakdown (relative residual > 1e4), not slow convergence — raise, don't spin
CG_DIVERGENCE_FACTOR = 1e8


class CGError(RuntimeError):
    """Base of every structured ``cg_solve`` failure.

    Raised — never a hang — the Python-level iteration loop is bounded by
    ``max_iters`` and every residual sync is a finite device fetch.
    ``result`` (when not None) carries the best iterate reached as a
    partial :class:`CGResult` (``converged=False``): resume with
    ``cg_solve(..., x0_p=err.result.x_p)`` instead of restarting from zero.
    """

    def __init__(self, message: str, iterations: int, residual: float,
                 tol: float, result: "CGResult | None" = None):
        super().__init__(message)
        self.iterations = iterations
        self.residual = residual
        self.tol = tol
        self.result = result


class CGMaxItersError(CGError):
    """``cg_solve`` exhausted ``max_iters`` without reaching tolerance."""

    def __init__(self, iterations: int, residual: float, tol: float,
                 result: "CGResult | None" = None):
        super().__init__(
            f"CG did not converge: relative residual {residual:.3e} > tol "
            f"{tol:.1e} after {iterations} iterations",
            iterations, residual, tol, result,
        )


class CGDivergedError(CGError):
    """``cg_solve`` hit numerical breakdown: a NaN/Inf residual (poisoned
    operand, corrupted halo) or a residual exploding past
    :data:`CG_DIVERGENCE_FACTOR` x ``||b||^2``.  Structured and immediate —
    a solver fed corrupted data must fail loudly, not iterate forever."""

    def __init__(self, iterations: int, residual: float, tol: float,
                 result: "CGResult | None" = None, reason: str = "diverged"):
        super().__init__(
            f"CG {reason}: relative residual {residual:.3e} (tol {tol:.1e}) "
            f"after {iterations} iterations",
            iterations, residual, tol, result,
        )
        self.reason = reason


@dataclasses.dataclass
class CGResult:
    """One CG solve: the planar solution plus its residual history.

    ``residuals[i]`` is the relative residual ``||r|| / ||b||`` after
    iteration ``i + 1`` — the iterate-by-iterate series the convergence
    tier pins against :func:`cg_reference_solve`.  ``dslash_applies``
    counts the operator applications dispatched (one stencil pass per
    site-local iteration, two half-lattice Dslash passes per even/odd
    staggered one; the lagged check's extra iteration and a restart's
    ``A x0`` included).
    """

    x_p: jax.Array
    iterations: int
    residuals: list[float]
    converged: bool
    wall_s: float
    dslash_applies: int = 0


def stencil_apply_reference(u: jax.Array, v: jax.Array, L: int) -> jax.Array:
    """Plain-jnp 8-direction stencil on canonical complex arrays.

    ``u (S, 4, 3, 3)`` complex links, ``v (S, 3)`` complex vector field —
    no planar packing, no Pallas, no neighbor-table sharing with the kernel
    path beyond the geometry itself: the independent oracle the CG tier
    pins convergence against.
    """
    S = L**4
    glob, _local, _b = stencil_neighbor_tables(L, S, 1)
    out = jnp.zeros_like(v)
    for mu in range(layouts.LINKS):
        out = out + jnp.einsum("skl,sl->sk", u[:, mu], v[glob[mu]])
        out = out + jnp.einsum("slk,sl->sk", jnp.conj(u[:, mu]), v[glob[4 + mu]])
    return out


def cg_reference_solve(
    u: jax.Array,
    b: jax.Array,
    L: int,
    *,
    tol: float = 1e-6,
    max_iters: int = 200,
    sigma: float = CG_SHIFT,
) -> tuple[jax.Array, list[float], bool]:
    """Plain-jnp CG on the shifted operator ``A = sigma I + S`` — the
    convergence-pinning oracle for :meth:`ExecutionPlan.cg_solve`.

    Complex-arithmetic textbook CG on canonical arrays; returns
    ``(x, relative residuals per iteration, converged)``.  Never raises on
    exhaustion (the oracle reports, the plan enforces).
    """
    # u is an argument, not a closed-over constant: at L=32 it is 302 MB
    apply_j = jax.jit(lambda u, p: sigma * p + stencil_apply_reference(u, p, L))
    return _textbook_cg(apply_j, u, b, tol, max_iters)


# -- the even/odd staggered (Kogut-Susskind) operator ------------------------
#
# MILC's ks_congrad solves M^dag M x = b with M = 2m + D and the one-link
# staggered Dslash, in MILC's normalisation (no 1/2):
#
#     D v(x) = sum_mu eta_mu(x) [ U_mu(x) v(x + mu) - U_mu(x - mu)^dag v(x - mu) ]
#
# eta_x = 1, eta_y = (-1)^x, eta_z = (-1)^(x+y), eta_t = (-1)^(x+y+z), and the
# fermion is antiperiodic in t: as MILC's rephasing does, that sign is folded
# into the t links of the last time slice.  D only connects sites of opposite
# parity, so on even sites (M^dag M)_ee = 4 m^2 - D_eo D_oe, Hermitian and
# positive: the CG operator, applied as two half-lattice Dslash passes.

KS_OPERATOR = "ks_eo"
SITE_LOCAL_OPERATOR = "site_local"


def staggered_phases(L: int) -> np.ndarray:
    """``(4, L**4)`` float32: the sign each stored link U_mu(x) carries,
    eta_mu(x) times -1 on the t links of the last time slice."""
    s = np.arange(L**4, dtype=np.int64)
    x, y, z, t = s % L, s // L % L, s // L**2 % L, s // L**3
    eta = np.stack([np.ones_like(x), (-1) ** x, (-1) ** (x + y), (-1) ** (x + y + z)])
    eta[3] = np.where(t == L - 1, -eta[3], eta[3])
    return eta.astype(np.float32)


def staggered_neighbor_tables(L: int, half: int) -> np.ndarray:
    """``(2, 8, half)`` int32: for each site of parity 0 (even) and 1 (odd),
    in :func:`layouts.parity_sites` order and padded to ``half`` sites, the
    stencil's 8 neighbours (direction order of
    :func:`stencil_neighbor_tables`) as positions in the OTHER parity's
    half vector.  Padding sites point at themselves (their links are 0)."""
    S = L**4
    even, odd = layouts.parity_sites(L)
    if half < S // 2:
        raise ValueError(f"half lattice of {half} sites < {S // 2}")
    glob, _local, _b = stencil_neighbor_tables(L, S, 1)
    pos = np.empty(S, np.int64)
    pos[even] = np.arange(S // 2)
    pos[odd] = np.arange(S // 2)
    nbr = np.tile(np.arange(half, dtype=np.int64), (2, 2 * layouts.LINKS, 1))
    for par, sites in enumerate((even, odd)):
        nbr[par, :, : S // 2] = pos[glob[:, sites]]
    return nbr.astype(np.int32)


def split_parity(a: jax.Array, L: int) -> tuple[jax.Array, jax.Array]:
    """``(..., L**4)`` site-last array -> its even and odd sites
    ``(..., L**4 // 2)`` each, in :func:`layouts.parity_sites` order.

    In a row of fixed (t, z, y) the sites of one parity are every other x,
    starting at x = (t + z + y + parity) % 2: a shift by one site where the
    row starts odd, then every other site.  Site-last and gather-free, so
    the site axis stays the lane axis of every intermediate on a TPU."""
    odd_row = _odd_rows(jax.lax.div(_sites(L**4), L), L)
    shifted = jnp.roll(a, -1, axis=-1)

    def every_other(x: jax.Array) -> jax.Array:  # a strided slice, not a gather
        return jax.lax.slice_in_dim(x, 0, x.shape[-1], stride=2, axis=x.ndim - 1)

    return (every_other(jnp.where(odd_row, shifted, a)),
            every_other(jnp.where(odd_row, a, shifted)))


def roll_back(a: jax.Array, L: int, mu: int) -> jax.Array:
    """``(..., L**4)`` site-last array -> its value at x - mu at each site x
    (periodic): a roll of the flat site axis, corrected on the face where
    the coordinate wraps."""
    return _hop(a, _sites(L**4), L, L**mu, -1)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["fwd_e", "bwd_e", "fwd_o", "bwd_o"],
    meta_fields=["mass"])
@dataclasses.dataclass(frozen=True)
class StaggeredField:
    """A gauge field prepared for the even/odd staggered operator at one
    valence mass (:meth:`ExecutionPlan.prepare_staggered`).

    Per parity, planar ``(2, 36, half)`` links in the plan's word dtype:
    ``fwd`` = eta_mu(x) U_mu(x), ``bwd`` = -eta_mu(x) U_mu(x - mu), both with
    the antiperiodic t sign.  A site's neighbours sit in the other
    parity's vector (:func:`half_neighbors`).
    Passed to ``cg_solve`` / ``cg_iterate`` in place of a physical gauge
    lattice, it selects the operator ``4 m^2 - D_eo D_oe`` on even-site
    vectors (``pack_half``).
    """

    fwd_e: jax.Array
    bwd_e: jax.Array
    fwd_o: jax.Array
    bwd_o: jax.Array
    mass: float

    @property
    def sigma(self) -> float:
        """The shift of the normal operator: 4 m^2."""
        return 4.0 * self.mass * self.mass

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in jax.tree.leaves(self))


def staggered_apply_reference(u: jax.Array, v: jax.Array, L: int) -> jax.Array:
    """Plain-jnp staggered Dslash ``D v`` on canonical complex arrays.

    ``u (S, 4, 3, 3)``, ``v (S, 3)``: the textbook form, phases and the
    antiperiodic t boundary applied to the hops (not folded into links),
    the backward hop reading the neighbour's link — independent of the
    plan's preparation, as the oracle the kernel is tested against.
    """
    S = L**4
    glob, _local, _b = stencil_neighbor_tables(L, S, 1)
    s = np.arange(S)
    x, y, z, t = s % L, s // L % L, s // L**2 % L, s // L**3
    eta = [np.ones(S), (-1.0) ** x, (-1.0) ** (x + y), (-1.0) ** (x + y + z)]
    fwd_bc = [np.ones(S)] * 3 + [np.where(t == L - 1, -1.0, 1.0)]
    bwd_bc = [np.ones(S)] * 3 + [np.where(t == 0, -1.0, 1.0)]
    out = jnp.zeros_like(v)
    with jax.default_matmul_precision("highest"):
        for mu in range(layouts.LINKS):
            fwd = jnp.einsum("skl,sl->sk", u[:, mu], v[glob[mu]])
            back = jnp.einsum("slk,sl->sk", jnp.conj(u[glob[4 + mu], mu]),
                              v[glob[4 + mu]])
            f = jnp.asarray(eta[mu] * fwd_bc[mu], jnp.float32)[:, None]
            b = jnp.asarray(eta[mu] * bwd_bc[mu], jnp.float32)[:, None]
            out = out + f * fwd - b * back
    return out


def staggered_eo_reference_solve(
    u: jax.Array,
    b_e: jax.Array,
    L: int,
    mass: float,
    *,
    tol: float = 1e-6,
    max_iters: int = 500,
) -> tuple[jax.Array, list[float], bool]:
    """Plain-jnp CG on ``(4 m^2 - D_eo D_oe) x_e = b_e`` — the oracle of the
    staggered ``cg_solve``.

    ``b_e (S/2, 3)`` is an even-site vector (:func:`layouts.parity_sites`
    order).  The solve runs on full-lattice vectors that vanish on odd
    sites, where ``4 m^2 v - D(D v)`` is exactly the even-even block.
    Returns ``(x_e, relative residuals per iteration, converged)``.
    """
    even, _odd = layouts.parity_sites(L)
    sigma = 4.0 * mass * mass

    def op(u, p):
        with jax.default_matmul_precision("highest"):
            return sigma * p - staggered_apply_reference(
                u, staggered_apply_reference(u, p, L), L)

    full = jnp.zeros((L**4, layouts.SU3), jnp.complex64).at[even].set(b_e)
    x, residuals, converged = _textbook_cg(jax.jit(op), u, full, tol, max_iters)
    return x[even], residuals, converged


def _textbook_cg(apply_j, u, b, tol, max_iters):
    """Complex CG from x = 0 on canonical arrays; never raises on
    exhaustion (the oracle reports, the plan enforces)."""
    b_rs = float(jnp.sum(jnp.real(b) ** 2 + jnp.imag(b) ** 2))
    if b_rs == 0.0:
        return jnp.zeros_like(b), [], True
    x = jnp.zeros_like(b)
    r = b
    p = b
    rs = jnp.sum(jnp.real(r) ** 2 + jnp.imag(r) ** 2)
    residuals: list[float] = []
    for _ in range(max_iters):
        ap = apply_j(u, p)
        pap = jnp.real(jnp.vdot(p, ap))
        alpha = rs / pap
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(jnp.real(r) ** 2 + jnp.imag(r) ** 2)
        residuals.append(float(rs_new / b_rs) ** 0.5)
        if residuals[-1] <= tol:
            return x, residuals, True
        p = r + (rs_new / rs) * p
        rs = rs_new
    return x, residuals, False


class ExecutionPlan:
    """Compiled execution of one EngineConfig tuple on one mesh.

    Construct via :func:`build_plan` (or ``ExecutionPlan.build``) — the single
    construction site for every layout x variant x placement combination.

    Attributes:
        codec: :class:`~repro.core.su3.layouts.LayoutCodec` — canonical
            (S, 4, 3, 3) complex <-> physical layout conversions.
        kernel: the resolved :class:`~repro.core.su3.registry.KernelEntry`.
        mesh: the concrete mesh; 1-D ``("sites",)`` or 2-D
            ``("hosts", "devices")``.
        site_axes: mesh axes the site dimension shards over (host-major).
        is_multi_host: mesh carries a host axis of size > 1.
        padded_sites: global site count padded so every device shard is a
            whole number of Pallas tiles.
        sharding / replicated: the lattice / scalar NamedShardings.
        step: jitted ``(a_phys, b_planar) -> c_phys`` — ONE dispatch, output
            sharded like the input (the chain stays device-local).
    """

    def __init__(self, cfg: EngineConfig, mesh: jax.sharding.Mesh | MeshSpec):
        self.cfg = cfg
        mesh = resolve_mesh(mesh)
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size)
        self.site_axes = dist_sharding.lattice_site_axes(mesh)
        self.is_multi_host = dist_sharding.lattice_is_multi_host(mesh)
        if cfg.placement not in PLACEMENTS:
            raise ValueError(f"unknown placement {cfg.placement!r}; one of {PLACEMENTS}")
        self.codec = layouts.make_codec(
            cfg.layout,
            tile=cfg.tile,
            dtype=cfg.dtype,
            accum_dtype=cfg.accum_dtype,
            compression=layouts.GaugeCompression(cfg.compression),
        )
        self.kernel = registry.get_kernel(cfg.variant)
        # Lattice padded so every device shard is a whole number of tiles.
        n = cfg.shape.n_sites
        chunk = self.n_devices * cfg.tile
        self.padded_sites = ((n + chunk - 1) // chunk) * chunk
        self.sharding = NamedSharding(
            mesh, dist_sharding.lattice_site_spec(self.codec, mesh)
        )
        self.replicated = NamedSharding(mesh, P())
        self.raw_step = make_raw_step(
            self.codec, self.kernel, tile=cfg.tile, mesh=mesh
        )
        self.step = jax.jit(self.raw_step, out_shardings=self.sharding)
        self._fused_steps: dict[int, Callable[[jax.Array, jax.Array], jax.Array]] = {}
        self._stencil_steps: dict[
            tuple[bool, int], Callable[[jax.Array, jax.Array], jax.Array]
        ] = {}
        self._stencil_tables: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None
        self._stencil_parts: dict[str, Any] | None = None
        self._cg_help: dict[str, Any] | None = None
        self._cg_applies: dict[tuple[bool, bool], Callable[..., Any]] = {}
        self._ks: dict[str, Any] | None = None
        # Phase tracer for the stencil schedule (repro.obs).  Disabled by
        # default: the untraced closures are byte-identical to pre-obs code.
        # When enabled, each schedule phase (exchange / interior / boundary)
        # blocks at its end so the span measures that phase — tracing
        # synchronizes the schedule (the only way to time a phase); the real
        # overlapped wall comes from an untraced run of the same step.
        self.tracer = NULL_TRACER
        # Fault plan for chaos testing (repro.chaos).  Disabled by default:
        # the same one-branch guard style as the tracer, so the fault-free
        # hot path is untouched.  When armed, the overlapped stencil
        # schedules consult the "halo" site after each exchange and apply
        # the drawn corruption to the ghost slabs before the boundary pass.
        self.faults = NULL_FAULT_PLAN

    @classmethod
    def build(
        cls, cfg: EngineConfig, mesh: jax.sharding.Mesh | MeshSpec | None = None
    ) -> "ExecutionPlan":
        return cls(cfg, resolve_mesh(mesh))

    @property
    def n_hosts(self) -> int:
        """Host-axis size of the mesh (1 on the legacy 1-D site mesh)."""
        if dist_sharding.LATTICE_HOST_AXIS in self.mesh.axis_names:
            return int(self.mesh.shape[dist_sharding.LATTICE_HOST_AXIS])
        return 1

    def halo(self) -> dist_sharding.HaloSpec:
        """Boundary geometry of this plan's per-host shards (see
        :func:`repro.distributed.sharding.halo_spec`); n_shards = n_hosts."""
        return dist_sharding.HaloSpec(
            L=self.cfg.L, n_shards=self.n_hosts, word_bytes=self.cfg.word_bytes
        )

    def lattice_batch_sharding(self) -> NamedSharding:
        """Sharding for a LEADING whole-lattice batch axis (request
        batches): the batch axis shards over the mesh's site
        axes — whole lattices per device, host-major — and every physical
        dimension is replicated.  The single owner of the layout ->
        physical-rank mapping for batched forms."""
        phys_ndim = 1 + {Layout.AOS: 2, Layout.SOA: 3, Layout.AOSOA: 4}[
            Layout(self.cfg.layout)
        ]
        axes = self.site_axes
        batch_axis = axes if len(axes) > 1 else axes[0]
        return NamedSharding(
            self.mesh, P(*((batch_axis,) + (None,) * (phys_ndim - 1)))
        )

    # -- fused multi-iteration stepping ---------------------------------------

    def fused_step(self, k: int) -> Callable[[jax.Array, jax.Array], jax.Array]:
        """One dispatch performing K chained multiplies (C fed back as A).

        ``fused_step(k)(a, b)`` equals ``step`` applied k times sequentially.
        The argument is donated (callers rebind ``a = fused(a, b)`` and never
        reuse the input) and the Pallas C-tile aliases A's buffer, so the
        chain is a true in-place VMEM-resident update.
        """
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if k not in self._fused_steps:
            raw = make_raw_step(
                self.codec, self.kernel, tile=self.cfg.tile, k_iters=k,
                alias=self.kernel.form == registry.PLANAR, mesh=self.mesh,
            )
            self._fused_steps[k] = jax.jit(
                raw, out_shardings=self.sharding, donate_argnums=(0,)
            )
        return self._fused_steps[k]

    # -- nearest-neighbor stencil (Dslash-style) -------------------------------

    @property
    def vec_sharding(self) -> NamedSharding:
        """Sharding of a planar color-vector field (2, 3, S): site axis over
        the mesh's site axes, components replicated — the vector field lives
        site-aligned with the lattice it belongs to."""
        ax = self.site_axes if len(self.site_axes) > 1 else self.site_axes[0]
        return NamedSharding(self.mesh, P(None, None, ax))

    def stencil_halo(self, depth: int = 1) -> dist_sharding.HaloSpec:
        """Halo spec of the stencil's *vector-field* exchange: same boundary
        geometry as :meth:`halo`, priced at 6 words/site (color 3-vectors
        travel, not gauge links) and at the plan's storage width.

        ``depth=2`` prices the communication-avoiding exchange that feeds two
        :meth:`stencil_step` applications per transfer (twice the ghost zone,
        half as many exchanges)."""
        return dist_sharding.HaloSpec(
            L=self.cfg.L,
            n_shards=self.n_hosts,
            word_bytes=self.cfg.word_bytes,
            words_per_site=dist_sharding.VECTOR_WORDS_PER_SITE,
            depth=depth,
        )

    def _stencil_geometry(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        if self._stencil_tables is None:
            self._stencil_tables = stencil_neighbor_tables(
                self.cfg.L, self.padded_sites, self.n_hosts
            )
        return self._stencil_tables

    def _neighbor_access(self, overlap: bool) -> str:
        """How a whole-lattice stencil or CG pass with ``overlap`` forms its
        neighbour operand: ``"roll"`` where it reads the exact periodic
        neighbours (no overlap, or one slab: no boundary sites, so the
        slab-local wrap is the periodic one), ``"gather"`` through the
        slab-local table otherwise.  The one decision
        :meth:`_neighbor_operand` acts on and the spans report."""
        n_boundary = self._stencil_geometry()[2].size
        return "gather" if (overlap and n_boundary) else "roll"

    def _neighbor_operand(self, v_p: jax.Array, overlap: bool) -> jax.Array:
        """The ``(8, 2, 3, N)`` neighbour operand of a whole-lattice pass:
        rolls of the site axis (:func:`periodic_neighbors`) or a gather
        through the slab-local table (:func:`gather_neighbors`), as
        :meth:`_neighbor_access` says."""
        if self._neighbor_access(overlap) == "roll":
            return periodic_neighbors(v_p, self.cfg.L)
        return gather_neighbors(v_p, self._stencil_geometry()[1])

    def _stencil_kernel(
        self, variant: str = STENCIL_VARIANT, sharded: bool = True,
        backward_links: bool = False,
    ) -> Callable[..., Any]:
        """The stencil-family kernel with the plan's kwargs bound.

        ``sharded`` runs it per shard of the plan's site sharding
        (:func:`site_local`): planar operands carry the site axis last —
        gauge ``(2, rows, S)``, gathered neighbors ``(8, 2, 3, S)``, vectors
        ``(2, 3, S)`` — and the CG coefficients are replicated.  Unsharded is
        the per-lattice body a caller vmaps and shards itself.
        ``backward_links`` takes the stencil's third operand, the backward
        hop's own ``(2, 36, S)`` links (the staggered operator).
        """
        kernel = registry.get_kernel(variant)
        if not kernel.supports_layout(self.codec.layout):
            raise ValueError(
                f"stencil kernel {kernel.name!r} does not support layout "
                f"{self.codec.layout.value!r}"
            )
        if self.codec.is_mixed_precision and not kernel.supports_accum_dtype():
            raise ValueError(
                f"stencil kernel {kernel.name!r} cannot accumulate at "
                f"{self.codec.accum_dtype!r} over {self.codec.dtype!r} storage"
            )
        if self.codec.is_compressed and not kernel.supports_compression():
            raise ValueError(
                f"stencil kernel {kernel.name!r} cannot stream two-row "
                f"compressed gauge (no reconstruct-on-load path)"
            )
        kw: dict[str, Any] = {"tile": self.cfg.tile}
        if self.codec.is_mixed_precision:
            kw["accum_dtype"] = self.codec.accum_dtype
        if self.codec.is_compressed:
            kw["compressed"] = True
        fn = functools.partial(kernel.fn, **kw)
        if not sharded:
            return fn
        ax = self.site_axes if len(self.site_axes) > 1 else self.site_axes[0]
        vec, nbr = P(None, None, ax), P(None, None, None, ax)
        if kernel.form == registry.STENCIL:
            specs = (vec, nbr, vec) if backward_links else (vec, nbr)
            return site_local(fn, self.mesh, specs, vec)
        return site_local(
            fn, self.mesh, (vec, nbr, nbr, vec, vec, P()), (vec, vec)
        )

    def _shard_pad(self, n: int) -> int:
        """Padding that makes a gathered ``n``-site pass (boundary, ring)
        split into whole tiles on every device."""
        return (-n) % (self.cfg.tile * self.n_devices)

    def raw_stencil_reference(
        self, sharded: bool = True
    ) -> Callable[[jax.Array, jax.Array], jax.Array]:
        """Unjitted reference stencil ``(u_phys, v_p) -> out_p``.

        Forms all 8 neighbor fields of the exact periodic table (rolls,
        :func:`periodic_neighbors`) and runs ONE kernel pass over every
        site — the bit-identity oracle the overlapped schedule is pinned
        against.  ``sharded=False`` is the
        per-lattice form the serving layer vmaps over request batches.
        """
        kernel = self._stencil_kernel(sharded=sharded)
        codec = self.codec

        def reference(u_phys: jax.Array, v_p: jax.Array) -> jax.Array:
            u_p = codec.planar_view(u_phys)
            v_nbr = self._neighbor_operand(v_p, overlap=False)  # (8, 2, 3, S)
            return kernel(u_p, v_nbr)

        return reference

    def stencil_reference_step(self) -> Callable[[jax.Array, jax.Array], jax.Array]:
        """Jitted non-overlapped reference stencil — ONE dispatch whose +-t
        neighbor rolls carry the halo traffic inline (compute waits for
        the exchange; the baseline the overlap schedule is measured against
        and pinned bit-identical to)."""
        return self.stencil_step(overlap=False)

    def stencil_step(
        self, overlap: bool | None = None, depth: int = 1
    ) -> Callable[[jax.Array, jax.Array], jax.Array]:
        """The stencil dispatch path: ``step(u_phys, v_p) -> out_p``.

        ``u_phys`` is the plan's physical gauge lattice, ``v_p`` the planar
        (2, 3, padded_sites) vector field (``codec.pack_vec``), and the
        result is the planar output vector field, sharded like ``v_p``.
        ``depth`` is the number of stencil applications the returned callable
        performs (``step(u, v)`` with depth=2 equals two depth-1 steps).

        overlap=False (the pinned reference): one jitted dispatch; the exact
        periodic neighbors by rolls of the site axis, kernel over all sites.

        overlap=True (default on multi-host meshes): the interior/boundary
        split schedule —

        1. **exchange** — dispatch the +-t ghost gathers of the boundary
           sites first; the cross-slab transfer is now in flight;
        2. **interior** — dispatch the full-lattice kernel pass whose +-t
           gathers wrap *within* each host slab (no cross-slab dependency,
           so it runs concurrently with the exchange); every interior
           site's result is already exact;
        3. **boundary** — once the ghosts land, recompute only the boundary
           sites with their true remote neighbors and scatter them over the
           interior pass's output.

        Because jax dispatch is asynchronous, step 2 is issued while step
        1's transfer is outstanding — on TPU the collective overlaps the
        interior kernel; on CPU interpret the three dispatches serialize
        (dispatch-order overlap only; see ROADMAP).  The boundary sites are
        computed twice — the classic overlap trade (arXiv:2112.01852) — and
        the result is bit-identical to the reference: same kernel, same
        per-site inputs, same accumulation order.

        depth=2 with overlap (communication avoidance): ONE ±t exchange
        carries the depth-2 ghost payload — the depth-1 ghosts plus every
        ``v`` value the *ring* (the ±t neighbors of the boundary sites)
        reads — and both applications run off it.  Step 2's boundary pass
        needs step 1's result at the ring; instead of a second exchange it
        is recomputed locally from the exchanged ``v`` (same kernel, same
        per-site inputs as the pass that produced it, so the recompute is
        bit-identical and the whole depth-2 step matches two depth-1
        steps).  Halves the exchange count per application at the cost of
        ``2 x ring`` extra boundary-size kernel work — the trade
        ``autotune.predict_stencil`` prices per mesh.
        """
        if depth not in (1, 2):
            raise ValueError(f"stencil exchange depth must be 1 or 2, got {depth}")
        if overlap is None:
            overlap = self.is_multi_host
        key = (bool(overlap), depth)
        if key not in self._stencil_steps:
            self._stencil_steps[key] = self._build_stencil_step(*key)
        return self._stencil_steps[key]

    def _stencil_overlap_parts(self) -> dict[str, Any]:
        """The jitted pieces every overlapped stencil schedule shares.

        One construction site so the depth-2 path reuses the SAME compiled
        interior/boundary programs as depth-1 — the bit-identity argument
        ("same kernel, same per-site inputs") then needs to cover only the
        ring recompute, not a re-derived schedule.
        """
        if self._stencil_parts is not None:
            return self._stencil_parts
        kernel = self._stencil_kernel()
        glob, _local, bidx = self._stencil_geometry()
        codec = self.codec
        out_sh = self.vec_sharding

        def interior_fn(u_phys: jax.Array, v_p: jax.Array) -> jax.Array:
            # slab-local neighbours only: independent of the in-flight exchange
            v_nbr = self._neighbor_operand(v_p, overlap=True)  # (8, 2, 3, S)
            return kernel(codec.planar_view(u_phys), v_nbr)

        parts: dict[str, Any] = {
            "interior_j": jax.jit(interior_fn, out_shardings=out_sh),
            "n_boundary": int(bidx.size),
        }
        if parts["n_boundary"]:
            n_boundary = parts["n_boundary"]
            # +-t ghosts: the true remote neighbors of the boundary sites
            ghost_fwd_idx, ghost_bwd_idx = glob[3][bidx], glob[7][bidx]
            xyz_idx = glob[(0, 1, 2, 4, 5, 6), :][:, bidx]  # shard-local dirs
            pad = self._shard_pad(n_boundary)

            def exchange_fn(v_p: jax.Array) -> tuple[jax.Array, jax.Array]:
                return v_p[:, :, ghost_fwd_idx], v_p[:, :, ghost_bwd_idx]

            def boundary_fn(
                u_phys: jax.Array,
                v_p: jax.Array,
                ghost_fwd: jax.Array,
                ghost_bwd: jax.Array,
                out_interior: jax.Array,
            ) -> jax.Array:
                u_b = codec.planar_view(u_phys)[:, :, bidx]  # (2, 36|24, B)
                v6 = gather_neighbors(v_p, xyz_idx)  # (6, 2, 3, B)
                v_nbr = jnp.concatenate(
                    [v6[:3], ghost_fwd[None], v6[3:], ghost_bwd[None]], axis=0
                )  # (8, 2, 3, B) in direction order
                if pad:
                    u_b = jnp.pad(u_b, ((0, 0), (0, 0), (0, pad)))
                    v_nbr = jnp.pad(v_nbr, ((0, 0), (0, 0), (0, 0), (0, pad)))
                out_b = kernel(u_b, v_nbr)[:, :, :n_boundary]
                return out_interior.at[:, :, bidx].set(out_b)

            parts.update(
                exchange_j=jax.jit(exchange_fn),
                boundary_j=jax.jit(boundary_fn, out_shardings=out_sh),
                ghost_fwd_idx=ghost_fwd_idx,
                ghost_bwd_idx=ghost_bwd_idx,
            )
        self._stencil_parts = parts
        return parts

    def _stencil_trace_attrs(self, overlap: bool, depth: int) -> dict[str, Any]:
        """Attrs every ``stencil.step`` span carries — the join key the
        attribution report matches against ``autotune.predict_stencil``."""
        from repro.kernels.su3_stencil import STENCIL_FLOPS_PER_SITE

        cfg = self.cfg
        return {
            "L": cfg.L, "tile": cfg.tile, "dtype": cfg.dtype,
            "compression": cfg.compression, "hosts": self.n_hosts,
            "overlap": bool(overlap), "depth": depth,
            "neighbours": self._neighbor_access(overlap),
            "flops": float(STENCIL_FLOPS_PER_SITE) * cfg.shape.n_sites * depth,
        }

    def _build_stencil_step(
        self, overlap: bool, depth: int = 1
    ) -> Callable[[jax.Array, jax.Array], jax.Array]:
        plan = self  # closures read plan.tracer at CALL time (set post-build)
        if not overlap:
            # ONE body for the reference: the same raw function the serving
            # layer vmaps, so the pinned bit-identity oracle and the served
            # stencil can never silently diverge
            ref = jax.jit(self.raw_stencil_reference(), out_shardings=self.vec_sharding)
            attrs = self._stencil_trace_attrs(False, depth)

            def serial(u_phys: jax.Array, v_p: jax.Array) -> jax.Array:
                tr = plan.tracer
                if not tr.enabled:
                    if depth == 1:
                        return ref(u_phys, v_p)
                    return ref(u_phys, ref(u_phys, v_p))
                with tr.span("stencil.step", **attrs):
                    out = ref(u_phys, v_p)
                    if depth == 2:
                        out = ref(u_phys, out)
                    out = jax.block_until_ready(out)
                return out

            return serial

        parts = self._stencil_overlap_parts()
        interior_j = parts["interior_j"]
        attrs = self._stencil_trace_attrs(True, depth)
        if parts["n_boundary"] == 0:
            # unsharded lattice: local wrap IS the periodic wrap, and there
            # is no exchange to avoid — depth just composes the interior pass

            def local_only(u_phys: jax.Array, v_p: jax.Array) -> jax.Array:
                tr = plan.tracer
                if not tr.enabled:
                    if depth == 1:
                        return interior_j(u_phys, v_p)
                    return interior_j(u_phys, interior_j(u_phys, v_p))
                with tr.span("stencil.step", **attrs):
                    for _ in range(depth):
                        with tr.span("stencil.interior"):
                            v_p = jax.block_until_ready(interior_j(u_phys, v_p))
                return v_p

            return local_only

        exchange_j, boundary_j = parts["exchange_j"], parts["boundary_j"]
        if depth == 1:

            def overlapped(u_phys: jax.Array, v_p: jax.Array) -> jax.Array:
                tr = plan.tracer
                if not tr.enabled:
                    ghosts = exchange_j(v_p)  # issued FIRST: transfer in flight
                    if plan.faults.enabled:
                        f = plan.faults.ask("halo", depth=1)
                        if f is not None:
                            ghosts = corrupt_ghosts(tuple(ghosts), f.action)
                    out_i = interior_j(u_phys, v_p)  # overlaps the exchange
                    return boundary_j(u_phys, v_p, *ghosts, out_i)
                # traced: each phase blocks so its span is a measurement —
                # phase times come from here, the hidden-vs-exposed wall
                # from an untraced run (see benchmarks/stencil.py)
                with tr.span("stencil.step", **attrs):
                    with tr.span("stencil.exchange"):
                        ghosts = jax.block_until_ready(exchange_j(v_p))
                    if plan.faults.enabled:
                        f = plan.faults.ask("halo", depth=1)
                        if f is not None:
                            ghosts = corrupt_ghosts(tuple(ghosts), f.action)
                    with tr.span("stencil.interior"):
                        out_i = jax.block_until_ready(interior_j(u_phys, v_p))
                    with tr.span("stencil.boundary"):
                        out = jax.block_until_ready(
                            boundary_j(u_phys, v_p, *ghosts, out_i))
                return out

            return overlapped

        return self._build_stencil_step2(parts)

    def _build_stencil_step2(
        self, parts: dict[str, Any]
    ) -> Callable[[jax.Array, jax.Array], jax.Array]:
        """The communication-avoiding double step (overlap, depth=2).

        Ring geometry: the ring is ``(+t, -t)`` neighbors of the boundary
        sites — exactly the sites whose step-1 results the second boundary
        pass consumes as ghosts.  ``exchange2`` ships the depth-2 payload in
        one dispatch (depth-1 ghosts + the 8-direction ``v`` neighborhoods of
        the ring); ``ring_j`` then recomputes step-1's output at the ring
        from that payload, so step 2 never exchanges.  A ring site is either
        interior to its owning shard (step 1 computed it through the local
        table, which equals the periodic table there) or a boundary site
        (step 1 computed it from the same glob-derived ghosts) — either way
        the recompute feeds the kernel the same per-site inputs, hence the
        bit-identity with two depth-1 steps.
        """
        kernel = self._stencil_kernel()
        glob, _local, _bidx = self._stencil_geometry()
        codec = self.codec
        interior_j, boundary_j = parts["interior_j"], parts["boundary_j"]
        n_boundary = parts["n_boundary"]

        ridx = np.concatenate([parts["ghost_fwd_idx"], parts["ghost_bwd_idx"]])
        ring_nbr_idx = glob[:, ridx]  # (8, 2B): every v site the ring reads
        n_ring = int(ridx.size)
        rpad = self._shard_pad(n_ring)

        def exchange2_fn(
            v_p: jax.Array,
        ) -> tuple[jax.Array, jax.Array, jax.Array]:
            # ONE dispatch shipping the whole depth-2 ghost zone: the
            # depth-1 ghosts (step 1's boundary pass) plus the v values
            # within two faces of the boundary (the ring recompute's reads)
            return (
                v_p[:, :, parts["ghost_fwd_idx"]],
                v_p[:, :, parts["ghost_bwd_idx"]],
                gather_neighbors(v_p, ring_nbr_idx),  # (8, 2, 3, 2B)
            )

        exchange2_j = jax.jit(exchange2_fn)

        def ring_fn(
            u_phys: jax.Array, ring_vnbr: jax.Array
        ) -> tuple[jax.Array, jax.Array]:
            u_r = codec.planar_view(u_phys)[:, :, ridx]  # (2, 36|24, 2B)
            if rpad:
                u_r = jnp.pad(u_r, ((0, 0), (0, 0), (0, rpad)))
                ring_vnbr = jnp.pad(
                    ring_vnbr, ((0, 0), (0, 0), (0, 0), (0, rpad))
                )
            w_r = kernel(u_r, ring_vnbr)[:, :, :n_ring]
            # step 1's output at (+t, -t) neighbors of the boundary — the
            # ghosts step 2's boundary pass would otherwise exchange
            return w_r[:, :, :n_boundary], w_r[:, :, n_boundary:]

        ring_j = jax.jit(ring_fn)

        plan = self
        attrs = self._stencil_trace_attrs(True, 2)

        def overlapped2(u_phys: jax.Array, v_p: jax.Array) -> jax.Array:
            tr = plan.tracer
            if not tr.enabled:
                g_fwd, g_bwd, ring_vnbr = exchange2_j(v_p)  # ONE exchange, 2 apps
                if plan.faults.enabled:
                    f = plan.faults.ask("halo", depth=2)
                    if f is not None:
                        g_fwd, g_bwd, ring_vnbr = corrupt_ghosts(
                            (g_fwd, g_bwd, ring_vnbr), f.action)
                out_1i = interior_j(u_phys, v_p)  # overlaps the exchange
                w = boundary_j(u_phys, v_p, g_fwd, g_bwd, out_1i)
                ring_w = ring_j(u_phys, ring_vnbr)  # recompute, don't re-exchange
                out_2i = interior_j(u_phys, w)
                return boundary_j(u_phys, w, *ring_w, out_2i)
            with tr.span("stencil.step", **attrs):
                with tr.span("stencil.exchange"):
                    g_fwd, g_bwd, ring_vnbr = jax.block_until_ready(
                        exchange2_j(v_p))
                if plan.faults.enabled:
                    f = plan.faults.ask("halo", depth=2)
                    if f is not None:
                        g_fwd, g_bwd, ring_vnbr = corrupt_ghosts(
                            (g_fwd, g_bwd, ring_vnbr), f.action)
                with tr.span("stencil.interior"):
                    out_1i = jax.block_until_ready(interior_j(u_phys, v_p))
                with tr.span("stencil.boundary"):
                    w = jax.block_until_ready(
                        boundary_j(u_phys, v_p, g_fwd, g_bwd, out_1i))
                with tr.span("stencil.ring"):
                    ring_w = jax.block_until_ready(ring_j(u_phys, ring_vnbr))
                with tr.span("stencil.interior"):
                    out_2i = jax.block_until_ready(interior_j(u_phys, w))
                with tr.span("stencil.boundary"):
                    out = jax.block_until_ready(
                        boundary_j(u_phys, w, *ring_w, out_2i))
            return out

        return overlapped2

    def init_stencil_data(self) -> tuple[jax.Array, jax.Array]:
        """The canonical stencil benchmark inputs under the plan's placement:
        ``(u_phys, v_p)`` with U entries (1, 0) and v entries (1/24, 0) —
        every output component of the 8-direction stencil is then exactly
        (1, 0) (see :func:`init_stencil_canonical`)."""
        a_phys, _b, _init_s, _scatter_s = self.init_data()
        _, v = layouts.on_host(init_stencil_canonical, self.cfg.shape.n_sites)
        return a_phys, self.pack_rhs(v)

    def unpack_vec(self, out_p: jax.Array, n_sites: int | None = None) -> jax.Array:
        """Planar stencil output -> canonical complex ``(n_sites, 3)`` on
        the host (default: the plan's live sites)."""
        n = n_sites or self.cfg.shape.n_sites
        return layouts.on_host(lambda x: self.codec.unpack_vec(x, n), out_p)

    def pack_gauge(self, u: jax.Array) -> jax.Array:
        """Canonical complex ``(n_sites, 4, 3, 3)`` gauge field -> physical
        packed layout under the plan's sharding, zero-padded to
        ``padded_sites`` (packed on the host).  Padding sites self-neighbor
        in the stencil tables and carry zero links, so they contribute
        nothing to any stencil or CG output."""

        def pack(u: jax.Array) -> jax.Array:
            pad = self.padded_sites - u.shape[0]
            if pad > 0:
                u = jnp.concatenate([u, jnp.zeros((pad,) + u.shape[1:], u.dtype)])
            return self.codec.pack(u)

        return jax.device_put(layouts.on_host(pack, u), self.sharding)

    def pack_rhs(self, b: jax.Array) -> jax.Array:
        """Canonical complex ``(n_sites, 3)`` vector field -> planar
        ``(2, 3, padded_sites)`` under the plan's vector sharding (zero
        padding keeps every CG reduction over the padded array exact)."""
        v_p = layouts.on_host(self.codec.pack_vec, b, self.padded_sites)
        return jax.device_put(v_p, self.vec_sharding)

    def pack_links(self, b: jax.Array) -> jax.Array:
        """Canonical link set ``(4, 3, 3)`` (or a batch of them) -> planar
        ``(..., 2, 36)``, replicated over the plan's mesh."""
        return jax.device_put(self.pack_links_on_host(b), self.replicated)

    def pack_links_on_host(self, b: jax.Array) -> jax.Array:
        """Canonical link set ``(4, 3, 3)`` (or a batch of them) -> planar
        ``(..., 2, 36)``, on the host."""
        pack = self.codec.pack_b if b.ndim == 3 else jax.vmap(self.codec.pack_b)
        return layouts.on_host(pack, b)

    def verify_stencil(self, out_p: jax.Array) -> bool:
        """Fixed-point check for :meth:`init_stencil_data` inputs: every
        output component must be (1, 0) within the storage dtype's tolerance.

        Two-row compressed plans see a DIFFERENT fixed point: the canonical
        uniform lattice is not SU(3), so the reconstructed third row is
        ``conj(r0 x r1) = 0`` rather than the stored all-ones row, and the
        8-direction sum lands on ``4 (U + U^T) v = (5/6, 5/6, 1/3)`` per
        component (computed here from the reconstructed link, not hardcoded).
        """
        c = self.unpack_vec(jax.device_get(out_p))
        if self.codec.is_compressed:
            u = np.ones((layouts.SU3, layouts.SU3))
            u[2] = 0.0  # reconstructed uniform link: row 2 = conj(r0 x r1) = 0
            expected = jnp.asarray(
                layouts.LINKS * (u + u.T) @ np.full(layouts.SU3, 1.0 / 24.0)
            )
        else:
            expected = jnp.asarray(1.0)
        tol = verify_tolerance(
            self.cfg.dtype, self.cfg.accum_dtype, reconstruct=self.codec.is_compressed
        )
        return bool(
            jnp.max(jnp.abs(jnp.real(c) - expected)) < tol
            and jnp.max(jnp.abs(jnp.imag(c))) < tol
        )

    # -- the even/odd staggered operator ----------------------------------------

    @property
    def half_sites(self) -> int:
        """Sites per parity, padded so every device shard is whole tiles."""
        chunk = self.n_devices * self.cfg.tile
        return -(-(self.cfg.shape.n_sites // 2) // chunk) * chunk

    def _ks_parts(self) -> dict[str, Any]:
        """The staggered operator's phases (:func:`staggered_phases`) and
        jitted pieces, built once per plan.

        ``prepare`` folds the phases into the links, rolls each direction's
        links one site on for the backward hop (:func:`roll_back`) and
        splits both by parity (:func:`split_parity`); ``dslash`` is one
        half-lattice pass ``D_{p,1-p} v``: the other parity's vector rolled
        to each neighbour (:func:`half_neighbors`) and one stencil kernel
        with backward links;
        ``shift`` the normal operator's epilogue ``sigma p - s``.
        """
        if self._ks is not None:
            return self._ks
        if self.codec.is_compressed:
            raise ValueError(
                "the staggered operator needs full link rows: its phase-folded "
                "links are not SU(3), so two-row storage cannot rebuild row 2")
        L, H, f32 = self.cfg.L, self.half_sites, jnp.float32
        S, rows = L**4, layouts.SU3 * layouts.SU3
        kernel = self._stencil_kernel(backward_links=True)
        vec_sh = self.vec_sharding

        def prepare(u_p, phases):
            signed = u_p[:, :, :S] * jnp.repeat(phases, rows, axis=0).astype(u_p.dtype)
            back = -jnp.concatenate(
                [roll_back(signed[:, rows * mu: rows * (mu + 1)], L, mu)
                 for mu in range(layouts.LINKS)], axis=1)
            fwd_e, fwd_o = split_parity(signed, L)
            bwd_e, bwd_o = split_parity(back, L)
            pad = ((0, 0), (0, 0), (0, H - S // 2))  # zero links: zero outputs
            return tuple(jnp.pad(a, pad) for a in (fwd_e, bwd_e, fwd_o, bwd_o))

        def dslash(fwd, bwd, v, parity):
            return kernel(fwd, half_neighbors(v, L, parity), bwd)

        def shift(p, sigma, s):
            return (sigma.astype(f32) * p.astype(f32) - s.astype(f32)).astype(p.dtype)

        self._ks = dict(
            phases=staggered_phases(L),
            prepare=jax.jit(prepare, out_shardings=(vec_sh,) * 4),
            dslash=jax.jit(dslash, out_shardings=vec_sh, static_argnames="parity"),
            shift=jax.jit(shift, out_shardings=vec_sh),
        )
        return self._ks

    def prepare_staggered(self, u: jax.Array, mass: float) -> StaggeredField:
        """Canonical gauge field ``(L**4, 4, 3, 3)`` -> the resident
        :class:`StaggeredField` of the even/odd staggered operator at valence
        mass ``mass``: packed (on the host, as :meth:`pack_gauge`), then, on
        the device, each link times its phase, each direction's links rolled
        one site on for the backward hop, both split by parity.  Run once per
        field; the field's solves then move only vectors."""
        if not mass > 0:
            raise ValueError(f"the staggered mass must be > 0, got {mass}")
        parts = self._ks_parts()
        tr = self.tracer
        with tr.span("ks.prepare", L=self.cfg.L, mass=float(mass)) as span:
            u_p = self.codec.planar_view(self.pack_gauge(u))
            links = parts["prepare"](u_p, parts["phases"])
            field = StaggeredField(*links, mass=float(mass))
            if tr.enabled:
                jax.block_until_ready(field)
                span.set(bytes=field.nbytes)
        return field

    def dslash_half(self, field: StaggeredField, v_p: jax.Array, parity: int) -> jax.Array:
        """One half-lattice Dslash: ``D_eo v`` (``parity=0``, v odd) or
        ``D_oe v`` (``parity=1``, v even), planar ``(2, 3, half_sites)``."""
        if parity == 0:
            return self._ks_parts()["dslash"](field.fwd_e, field.bwd_e, v_p, parity=0)
        return self._ks_parts()["dslash"](field.fwd_o, field.bwd_o, v_p, parity=1)

    def _ks_apply(self) -> Callable[..., Any]:
        """The staggered CG apply ``(field, r_p, p_p, coefs) -> (p', ap)``:
        ``p' = r + beta p`` (the composed path's shared axpy), then
        ``ap = 4 m^2 p' - D_eo (D_oe p')`` as two half-lattice passes."""
        parts, h = self._ks_parts(), self._cg_helpers()

        def apply(field, r_p, p_p, coefs):
            p_new = h["axpy"](r_p, coefs[0, 0], p_p)
            w = self.dslash_half(field, p_new, 1)  # D_oe p': odd sites
            s = self.dslash_half(field, w, 0)  # D_eo D_oe p': even sites
            return p_new, parts["shift"](p_new, coefs[0, 1], s)

        return apply

    def pack_half(self, b: jax.Array) -> jax.Array:
        """Canonical half-lattice vector ``(L**4 // 2, 3)`` (one parity, in
        :func:`layouts.parity_sites` order) -> planar ``(2, 3, half_sites)``
        under the plan's vector sharding, zero-padded."""
        v_p = layouts.on_host(self.codec.pack_vec, b, self.half_sites)
        return jax.device_put(v_p, self.vec_sharding)

    def unpack_half(self, x_p: jax.Array) -> jax.Array:
        """Planar half-lattice vector -> canonical ``(L**4 // 2, 3)`` on the
        host."""
        return self.unpack_vec(x_p, self.cfg.shape.n_sites // 2)

    def _cg_operator(self, u_phys: Any, sigma: float | None, fused: bool,
                     overlap: bool | None) -> tuple[Callable[..., Any], float]:
        """The CG apply and its shift for what ``u_phys`` is: a
        :class:`StaggeredField` selects ``4 m^2 - D_eo D_oe`` (shift from
        its mass; ``fused``/``overlap`` do not apply), a physical gauge
        lattice the site-local ``sigma I + S`` (default :data:`CG_SHIFT`)."""
        if isinstance(u_phys, StaggeredField):
            if sigma is not None:
                raise ValueError("the staggered operator's shift is 4 m^2 from "
                                 "its field's mass; pass no sigma")
            return self._ks_apply(), u_phys.sigma
        if overlap is None:
            overlap = self.is_multi_host
        return self._cg_apply(fused, bool(overlap)), CG_SHIFT if sigma is None else sigma

    # -- conjugate-gradient solver (fused stencil+axpy iteration) --------------

    def _cg_helpers(self) -> dict[str, Any]:
        """Jitted scalar/elementwise CG pieces, built once per plan.

        Shared VERBATIM by the fused and composed iteration paths, so the
        fused-vs-composed bit-identity contract reduces to the kernel-level
        argument (same f32 expressions on the same operands): alpha, beta,
        the x/r updates and both global reductions are literally the same
        compiled programs on both paths.
        """
        if self._cg_help is not None:
            return self._cg_help
        vec_sh, rep = self.vec_sharding, self.replicated
        f32 = jnp.float32

        def _rr(v: jax.Array) -> jax.Array:
            v = v.astype(f32)
            return jnp.sum(v * v)

        def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
            return jnp.sum(a.astype(f32) * b.astype(f32))

        def _update(x, r, p, ap, alpha):
            a = alpha.astype(f32)
            return (
                (x.astype(f32) + a * p.astype(f32)).astype(x.dtype),
                (r.astype(f32) - a * ap.astype(f32)).astype(r.dtype),
            )

        def _axpy(r, beta, p):  # composed-path search-direction update
            return (r.astype(f32) + beta.astype(f32) * p.astype(f32)).astype(r.dtype)

        def _shift(p, sigma, s):  # composed-path shifted apply epilogue
            return (
                sigma.astype(f32) * p.astype(f32) + s.astype(f32)
            ).astype(p.dtype)

        def _coef(beta, sigma):
            return jnp.stack(
                [jnp.asarray(beta, f32), jnp.asarray(sigma, f32)]
            ).reshape(1, 2)

        self._cg_help = {
            "rr": jax.jit(_rr, out_shardings=rep),
            "dot": jax.jit(_dot, out_shardings=rep),
            "update": jax.jit(_update, out_shardings=(vec_sh, vec_sh)),
            "axpy": jax.jit(_axpy, out_shardings=vec_sh),
            "shift": jax.jit(_shift, out_shardings=vec_sh),
            "scal": jax.jit(lambda num, den: num / den, out_shardings=rep),
            "coef": jax.jit(_coef, out_shardings=rep),
            "init": jax.jit(
                lambda b: (jnp.zeros_like(b), b, b),
                out_shardings=(vec_sh, vec_sh, vec_sh),
            ),
        }
        return self._cg_help

    def _cg_apply(self, fused: bool, overlap: bool) -> Callable[..., Any]:
        """The per-iteration apply ``(u_phys, r_p, p_p, coefs) -> (p', ap)``
        with ``p' = r + beta p`` and ``ap = sigma p' + S(p')``.

        fused=True: ONE pallas_call per pass — the search-direction axpy is
        formed on the gathered (r, p) neighbor tiles in VMEM and the raw
        apply S(p') lands in the same pass (``registry.STENCIL_AXPY`` form);
        the sigma shift then runs in the SAME shared jitted program as the
        composed path, which is what pins f32 iterates bit-identical
        (an in-kernel shift FMA-contracts differently across programs).
        On a multi-host mesh with ``overlap`` the pass splits into the same
        exchange / interior / boundary schedule as ``stencil_step`` — the
        ±t ghosts of BOTH r and p ship first, the slab-local fused pass
        overlaps the transfer (p' is elementwise, so the interior pass's p'
        is already exact everywhere; only ap needs the boundary scatter).

        fused=False: the composed oracle — the shared jitted axpy, then
        ``stencil_step(overlap)``, then the shared shift epilogue.  At f32
        storage its iterates are pinned bit-identical to the fused path.
        """
        key = (bool(fused), bool(overlap))
        if key in self._cg_applies:
            return self._cg_applies[key]
        plan = self
        h = self._cg_helpers()

        if not fused:
            step = self.stencil_step(overlap=overlap)

            def composed(u_phys, r_p, p_p, coefs):
                beta, sigma = coefs[0, 0], coefs[0, 1]
                p_new = h["axpy"](r_p, beta, p_p)
                return p_new, h["shift"](p_new, sigma, step(u_phys, p_new))

            self._cg_applies[key] = composed
            return composed

        kernel = self._stencil_kernel(CG_VARIANT)
        glob, _local, bidx = self._stencil_geometry()
        codec = self.codec
        vec_sh = self.vec_sharding
        n_boundary = int(bidx.size)

        def whole_fn(u_phys, r_p, p_p, coefs):
            r_nbr = self._neighbor_operand(r_p, overlap)  # (8, 2, 3, S)
            p_nbr = self._neighbor_operand(p_p, overlap)
            return kernel(codec.planar_view(u_phys), r_nbr, p_nbr, r_p, p_p, coefs)

        whole_j = jax.jit(whole_fn, out_shardings=(vec_sh, vec_sh))

        if not (overlap and n_boundary):
            # single shard (or overlap off): the periodic neighbors and one
            # fused pass; nothing to exchange
            def fused_whole(u_phys, r_p, p_p, coefs):
                tr = plan.tracer
                if not tr.enabled:
                    p_new, s = whole_j(u_phys, r_p, p_p, coefs)
                    return p_new, h["shift"](p_new, coefs[0, 1], s)
                with tr.span("cg.interior"):
                    p_new, s = jax.block_until_ready(
                        whole_j(u_phys, r_p, p_p, coefs))
                return p_new, h["shift"](p_new, coefs[0, 1], s)

            self._cg_applies[key] = fused_whole
            return fused_whole

        # overlap schedule: same geometry as _stencil_overlap_parts, but the
        # exchange ships BOTH fields' ±t ghosts (p' at the boundary is
        # r_ghost + beta p_ghost — computed in-kernel, never exchanged);
        # the boundary pass scatters the RAW apply S(p') and the sigma shift
        # runs once on the merged array via the shared epilogue
        ghost_fwd_idx, ghost_bwd_idx = glob[3][bidx], glob[7][bidx]
        xyz_idx = glob[(0, 1, 2, 4, 5, 6), :][:, bidx]
        pad = self._shard_pad(n_boundary)

        def exchange_fn(r_p, p_p):
            return (
                r_p[:, :, ghost_fwd_idx], r_p[:, :, ghost_bwd_idx],
                p_p[:, :, ghost_fwd_idx], p_p[:, :, ghost_bwd_idx],
            )

        def boundary_fn(u_phys, r_p, p_p, r_gf, r_gb, p_gf, p_gb, coefs, s_i):
            u_b = codec.planar_view(u_phys)[:, :, bidx]  # (2, 36|24, B)
            r6 = gather_neighbors(r_p, xyz_idx)  # (6, 2, 3, B)
            p6 = gather_neighbors(p_p, xyz_idx)
            r_nbr = jnp.concatenate(
                [r6[:3], r_gf[None], r6[3:], r_gb[None]], axis=0
            )
            p_nbr = jnp.concatenate(
                [p6[:3], p_gf[None], p6[3:], p_gb[None]], axis=0
            )
            r_b, p_b = r_p[:, :, bidx], p_p[:, :, bidx]
            if pad:
                u_b = jnp.pad(u_b, ((0, 0), (0, 0), (0, pad)))
                r_nbr = jnp.pad(r_nbr, ((0, 0), (0, 0), (0, 0), (0, pad)))
                p_nbr = jnp.pad(p_nbr, ((0, 0), (0, 0), (0, 0), (0, pad)))
                r_b = jnp.pad(r_b, ((0, 0), (0, 0), (0, pad)))
                p_b = jnp.pad(p_b, ((0, 0), (0, 0), (0, pad)))
            _p_new_b, s_b = kernel(u_b, r_nbr, p_nbr, r_b, p_b, coefs)
            return s_i.at[:, :, bidx].set(s_b[:, :, :n_boundary])

        exchange_j = jax.jit(exchange_fn)
        boundary_j = jax.jit(boundary_fn, out_shardings=vec_sh)

        def fused_overlapped(u_phys, r_p, p_p, coefs):
            tr = plan.tracer
            if not tr.enabled:
                ghosts = exchange_j(r_p, p_p)  # ±t transfer in flight
                p_new, s_i = whole_j(u_phys, r_p, p_p, coefs)  # slab-local
                s = boundary_j(u_phys, r_p, p_p, *ghosts, coefs, s_i)
                return p_new, h["shift"](p_new, coefs[0, 1], s)
            with tr.span("cg.exchange"):
                ghosts = jax.block_until_ready(exchange_j(r_p, p_p))
            with tr.span("cg.interior"):
                p_new, s_i = jax.block_until_ready(whole_j(u_phys, r_p, p_p, coefs))
            with tr.span("cg.boundary"):
                s = jax.block_until_ready(
                    boundary_j(u_phys, r_p, p_p, *ghosts, coefs, s_i))
            return p_new, h["shift"](p_new, coefs[0, 1], s)

        self._cg_applies[key] = fused_overlapped
        return fused_overlapped

    def cg_state_init(
        self,
        b_p: jax.Array,
        x0_p: jax.Array | None = None,
        *,
        u_phys: jax.Array | StaggeredField | None = None,
        sigma: float | None = None,
        fused: bool = True,
        overlap: bool | None = None,
    ) -> dict[str, Any]:
        """Initial CG state for planar right-hand side ``b_p``: x = 0,
        r = b, p-seed = b, beta = 0 — the first :meth:`cg_iterate` then
        forms ``p_1 = r + 0 p = b``, the textbook start.

        With ``x0_p`` (a prior partial iterate, e.g. ``err.result.x_p`` off
        a :class:`CGError`) this is a CG *restart*: ``r_0 = b - A x_0`` is
        computed with the same apply/epilogue programs as the iterations
        (``u_phys`` is required for that one application), the search
        direction reseeds from ``r_0`` — resumed work is not thrown away,
        only the Krylov history is."""
        h = self._cg_helpers()
        if x0_p is None:
            x, r, p = h["init"](b_p)
            return {
                "x": x, "r": r, "p": p, "rs": h["rr"](r),
                "beta": jnp.float32(0.0), "iterations": 0,
            }
        if u_phys is None:
            raise ValueError("resuming cg_state_init from x0_p needs u_phys "
                             "to form r0 = b - A x0")
        apply_fn, sigma = self._cg_operator(u_phys, sigma, fused, overlap)
        zeros, _r, _p = h["init"](b_p)
        # beta = 0 makes the apply's p' = x0 exactly, so ap = A x0 comes out
        # of the same compiled pass the iterations use
        _x0, ax0 = apply_fn(u_phys, x0_p, zeros, h["coef"](0.0, sigma))
        # shared update with p = 0, alpha = 1: x stays x0, r = b - A x0
        x, r = h["update"](x0_p, b_p, zeros, ax0, jnp.float32(1.0))
        return {
            "x": x, "r": r, "p": r, "rs": h["rr"](r),
            "beta": jnp.float32(0.0), "iterations": 0,
        }

    def cg_iterate(
        self,
        u_phys: jax.Array | StaggeredField,
        state: dict[str, Any],
        *,
        sigma: float | None = None,
        fused: bool = True,
        overlap: bool | None = None,
    ) -> dict[str, Any]:
        """Advance the CG state by ONE iteration; everything stays device-
        resident.  The caller decides when to sync on ``state["rs"]`` (the
        global residual reduction): ``cg_solve`` fetches it one iteration
        late, so the reduce's host round trip overlaps the next iteration's
        interior pass; the serving layer syncs per scheduling turn.
        ``u_phys`` selects the operator as in :meth:`cg_solve`.
        """
        h = self._cg_helpers()
        apply_fn, sigma = self._cg_operator(u_phys, sigma, fused, overlap)
        coefs = h["coef"](state["beta"], sigma)
        p, ap = apply_fn(u_phys, state["r"], state["p"], coefs)
        alpha = h["scal"](state["rs"], h["dot"](p, ap))
        x, r = h["update"](state["x"], state["r"], p, ap, alpha)
        rs_new = h["rr"](r)
        return {
            "x": x, "r": r, "p": p, "rs": rs_new,
            "beta": h["scal"](rs_new, state["rs"]),
            "iterations": state["iterations"] + 1,
        }

    def cg_solve(
        self,
        u_phys: jax.Array | StaggeredField,
        b_p: jax.Array,
        *,
        tol: float = 1e-6,
        max_iters: int = 200,
        sigma: float | None = None,
        fused: bool = True,
        overlap: bool | None = None,
        x0_p: jax.Array | None = None,
    ) -> CGResult:
        """Conjugate gradients to ``||r|| <= tol ||b||`` on one of two
        operators, chosen by what ``u_phys`` is:

        * a physical gauge lattice: ``A = sigma I + S``, S the site-local-
          adjoint stencil (Hermitian only on fields constant along each
          link's own direction), full-lattice vectors (:meth:`pack_rhs`);
        * a :class:`StaggeredField` (:meth:`prepare_staggered`): MILC's
          even/odd staggered normal operator ``4 m^2 - D_eo D_oe`` on
          even-site vectors (:meth:`pack_half`), two half-lattice Dslash
          passes per iteration; ``sigma``, ``fused`` and ``overlap`` do not
          apply (on several devices the passes run on the site sharding
          without an overlap schedule).

        On the site-local operator each iteration is one fused
        stencil+axpy pallas pass (``fused=True``; ``fused=False`` composes
        ``stencil_step`` + the shared axpy — the bit-identity oracle) plus
        the shared scalar updates.  Convergence is checked one iteration
        LATE: iteration ``i+1`` is dispatched before iteration ``i``'s
        residual scalar is pulled to the host, so the global reduction
        (``cg.reduce`` span) overlaps the in-flight interior pass — the CG
        analogue of the stencil's exchange/interior overlap.  At most one
        extra iteration is dispatched past convergence.

        Args:
            u_phys: the plan's physical gauge lattice (``init_data`` /
                ``codec.pack`` form, padded to ``padded_sites``), or a
                :class:`StaggeredField`.
            b_p: planar right-hand side ``(2, 3, padded_sites)``
                (``codec.pack_vec``), or ``(2, 3, half_sites)`` for the
                staggered operator, sharded like :attr:`vec_sharding`.
            tol: relative residual target.
            max_iters: hard bound; exhaustion RAISES :class:`CGMaxItersError`
                (never hangs — the loop is host-bounded).
            sigma: the site-local operator's SPD shift (default
                :data:`CG_SHIFT`).
            fused / overlap: iteration body selection, as above.
            x0_p: optional warm start (a prior partial iterate) — restarts
                from ``r0 = b - A x0`` via :meth:`cg_state_init` instead of
                from zero.

        Raises:
            CGMaxItersError: tolerance not reached within ``max_iters``;
                ``err.result`` carries the best iterate for resume.
            CGDivergedError: NaN/Inf residual or residual blow-up past
                :data:`CG_DIVERGENCE_FACTOR` x ``||b||^2`` — numerical
                breakdown, surfaced immediately with the best iterate.
        """
        tr = self.tracer
        h = self._cg_helpers()
        t0 = time.perf_counter()
        b_rs = float(jax.device_get(h["rr"](b_p)))
        if b_rs == 0.0:
            x, _r, _p = h["init"](b_p)
            return CGResult(x_p=x, iterations=0, residuals=[], converged=True,
                            wall_s=time.perf_counter() - t0)
        if not math.isfinite(b_rs):
            raise CGDivergedError(0, float("nan"), tol,
                                  reason="non-finite right-hand side")
        staggered = isinstance(u_phys, StaggeredField)
        operator = KS_OPERATOR if staggered else SITE_LOCAL_OPERATOR
        # the staggered Dslash has one access: rolls of the half lattice
        neighbours = "roll" if staggered else self._neighbor_access(
            self.is_multi_host if overlap is None else overlap)
        per_iter = 2 if staggered else 1  # half-lattice passes vs one stencil
        stop2 = (tol * tol) * b_rs
        state = self.cg_state_init(b_p, x0_p, u_phys=u_phys, sigma=sigma,
                                   fused=fused, overlap=overlap)
        residuals: list[float] = []
        prev: tuple[jax.Array, jax.Array] | None = None  # (x_i, rs_i)
        best: tuple[jax.Array, float, int] | None = None  # (x, rs_host, iter)
        dispatched = 0  # iterations dispatched, the lagged one included

        def result(x: jax.Array, iterations: int, converged: bool) -> CGResult:
            applies = per_iter * dispatched + (0 if x0_p is None else per_iter)
            if tr.enabled:
                tr.count("dslash_applies", applies)
            return CGResult(x_p=x, iterations=iterations,
                            residuals=list(residuals), converged=converged,
                            wall_s=time.perf_counter() - t0,
                            dslash_applies=applies)

        def partial(iterations: int) -> CGResult | None:
            # the best-so-far iterate, packaged for x0_p resume
            if best is None:
                return None
            return result(best[0], iterations, False)

        def check(rs_host: float, x: jax.Array, it: int) -> None:
            # NaN/Inf or blow-up means breakdown, not slow convergence
            nonlocal best
            if not math.isfinite(rs_host):
                raise CGDivergedError(
                    it, float("nan"), tol, partial(it),
                    reason="non-finite residual")
            if rs_host > CG_DIVERGENCE_FACTOR * b_rs:
                raise CGDivergedError(
                    it, (rs_host / b_rs) ** 0.5, tol, partial(it))
            if best is None or rs_host < best[1]:
                best = (x, rs_host, it)

        for i in range(1, max_iters + 1):
            if tr.enabled:
                # traced: the iter span blocks so it measures the iteration —
                # tracing synchronizes, as with the stencil schedule spans
                with tr.span("cg.iter", it=i, fused=bool(fused) and not staggered,
                             operator=operator, neighbours=neighbours):
                    state = self.cg_iterate(
                        u_phys, state, sigma=sigma, fused=fused, overlap=overlap)
                    jax.block_until_ready(state["rs"])
            else:
                state = self.cg_iterate(
                    u_phys, state, sigma=sigma, fused=fused, overlap=overlap)
            dispatched = i
            if prev is not None:
                # lagged check: iteration i is already in flight; this fetch
                # is the previous iteration's global reduce landing
                if tr.enabled:
                    with tr.span("cg.reduce", it=i - 1):
                        rs_host = float(jax.device_get(prev[1]))
                else:
                    rs_host = float(jax.device_get(prev[1]))
                residuals.append((rs_host / b_rs) ** 0.5)
                if rs_host <= stop2:
                    return result(prev[0], i - 1, True)
                check(rs_host, prev[0], i - 1)
            prev = (state["x"], state["rs"])
        rs_host = float(jax.device_get(prev[1]))
        residuals.append((rs_host / b_rs) ** 0.5)
        if rs_host <= stop2:
            return result(prev[0], max_iters, True)
        check(rs_host, prev[0], max_iters)
        raise CGMaxItersError(max_iters, (rs_host / b_rs) ** 0.5, tol,
                              partial(max_iters))

    # -- placement policies ----------------------------------------------------

    def init_data(self) -> tuple[jax.Array, jax.Array, float, float]:
        """Build the benchmark lattice under the plan's placement policy.

        Returns:
            ``(a_phys, b_planar, init_seconds, scatter_seconds)`` — the
            physical A lattice (sharded per the policy), the replicated
            planar B ``(2, 36)``, wall seconds of initialization, and the
            redistribution seconds (``host_scatter`` only; 0.0 otherwise).

        On a multi-host mesh the ``sharded`` policy goes through
        :func:`first_touch_init`: each host materializes only its contiguous
        site slab, host-locally — the fleet form of the paper's NUMA-aware
        object creation.  Single-host meshes keep the jit-with-sharded-
        outputs form (same result, bit-identical).
        """
        cfg = self.cfg

        def build() -> jax.Array:
            a, _ = init_canonical(self.padded_sites)
            return self.codec.pack(a)

        b_planar = self.codec.pack_b(init_canonical(1)[1])
        b_planar = jax.device_put(b_planar, self.replicated)

        t0 = time.perf_counter()
        scatter_s = 0.0
        if cfg.placement == "sharded":
            if self.is_multi_host:
                # Fleet form of the paper's fix: each host builds exactly its
                # slab of sites in host memory and places it on its own
                # devices — no global materialization, no redistribution.
                a_phys = first_touch_init(self.codec, self.sharding, self.padded_sites)
            else:
                # Paper's fix: jit the initializer with sharded outputs —
                # every device first-touches exactly its shard.
                a_phys = jax.jit(build, out_shardings=self.sharding)()
            a_phys.block_until_ready()
        elif cfg.placement == "host_scatter":
            # Failure mode: materialize on one device, then redistribute.
            a_single = jax.jit(build)()  # default device only
            a_single.block_until_ready()
            t1 = time.perf_counter()
            a_phys = jax.device_put(a_single, self.sharding)
            a_phys.block_until_ready()
            scatter_s = time.perf_counter() - t1
        else:  # replicated
            a_phys = jax.jit(build, out_shardings=self.replicated)()
            a_phys.block_until_ready()
        init_s = time.perf_counter() - t0
        return a_phys, b_planar, init_s, scatter_s

    # -- views / checks --------------------------------------------------------

    def unpack(self, c_phys: jax.Array, n_sites: int | None = None) -> jax.Array:
        """Physical C -> canonical complex on the host, sliced to ``n_sites``
        (default: the plan's live lattice sites)."""
        n = n_sites or self.cfg.shape.n_sites
        return layouts.on_host(lambda c: self.codec.unpack(c, n), c_phys)

    def verify(self, c_phys: jax.Array) -> bool:
        """su3_bench check: with A=(1,0), B=(1/3,0) every C element is (1,0).

        Two-row compressed plans check the STORED rows only: the canonical
        uniform lattice is not SU(3), so ``unpack``'s reconstructed third row
        is ``conj(r0 x r1) = 0`` by construction — a property of the codec,
        not of the multiply (whose stored output is exact; its rows 0/1
        depend only on A's rows 0/1).
        """
        c = self.unpack(jax.device_get(c_phys))
        if self.codec.is_compressed:
            c = c[:, :, : self.codec.stored_rows, :]
        tol = verify_tolerance(
            self.cfg.dtype, self.cfg.accum_dtype, reconstruct=self.codec.is_compressed
        )
        return bool(
            jnp.max(jnp.abs(jnp.real(c) - 1.0)) < tol
            and jnp.max(jnp.abs(jnp.imag(c))) < tol
        )

    def describe(self) -> str:
        """Compact plan identity for benchmark rows / logs.

        Single-host strings are unchanged from the 1-D-mesh era (bench rows
        stay comparable); multi-host plans append the host count.
        """
        c = self.cfg
        acc = f"+acc-{c.accum_dtype}" if c.is_mixed_precision else ""
        comp = "+two-row" if c.is_compressed else ""
        hosts = f"x{self.n_hosts}h" if self.is_multi_host else ""
        return (
            f"{c.layout.value}/{c.variant}/t{c.tile}/{c.placement}"
            f"@{self.n_devices}dev{hosts}/{c.dtype}{acc}{comp}"
        )


def build_plan(
    cfg: EngineConfig, mesh: jax.sharding.Mesh | MeshSpec | None = None
) -> ExecutionPlan:
    """THE construction site: config tuple -> compiled ExecutionPlan.

    Args:
        cfg: the tunable tuple (layout, variant, tile, placement, dtypes, L).
        mesh: ``None`` (1-D site mesh over every local device), a concrete
            ``jax.sharding.Mesh``, or a :class:`~repro.launch.mesh.MeshSpec`
            describing a (host, device) topology.

    Returns:
        A compiled :class:`ExecutionPlan` whose ``step`` / ``fused_step(k)``
        dispatch with the lattice sharded over the mesh's site axes.
    """
    return ExecutionPlan.build(cfg, mesh)


# Words of zeros ahead of the fetched words.  JAX's CPU client wraps a
# numpy buffer without a copy only at a 64-byte boundary, and the host buffer
# a TPU fetch lands in starts 16 bytes into a page; 12 words (48 bytes) put
# the words themselves on the boundary.  Where they land off it (the CPU
# backend's own buffers among them) the put copies them, once.
FETCH_HEAD = 12


def _relayout_sites(padded_sites: int) -> int:
    """Sites the relayout kernels run over: ``padded_sites`` rounded up to a
    whole number of 128-site blocks."""
    return -(-padded_sites // layouts.LANE) * layouts.LANE


class BatchedLatticeRunner:
    """Serve B independent lattices through one vmapped, sharded plan step.

    The "many users" scenario: each request carries its own (A, B) lattice
    pair; the runner shards the *batch* axis over the mesh (whole lattices per
    device) and runs every request through the same compiled plan in one
    dispatch — no per-request compilation or per-layout wiring.

    Batches that do not divide the device count are zero-padded and sliced.

    On a (host, device) mesh the *batch* axis shards over the same site axes
    (whole lattices per device, host-major) — one host's requests stay on
    that host's devices, which is what the serving layer's locality routing
    relies on.
    """

    def __init__(
        self, cfg: EngineConfig, mesh: jax.sharding.Mesh | MeshSpec | None = None
    ):
        self.plan = build_plan(cfg, mesh)
        self.cfg = cfg
        self.mesh = self.plan.mesh
        self.n_devices = self.plan.n_devices
        self._sharding = self.plan.lattice_batch_sharding()
        self._steps: dict[int, Callable[[jax.Array, jax.Array], jax.Array]] = {}
        self._codecs: dict[tuple[Any, ...], Callable[..., Any]] = {}
        # recorder of multiply's steps, read at call time (like plan.tracer)
        self.tracer = NULL_TRACER

    def _batched_step(self, k: int) -> Callable[[jax.Array, jax.Array], jax.Array]:
        if k not in self._steps:
            raw = make_raw_step(
                self.plan.codec, self.plan.kernel, tile=self.cfg.tile, k_iters=k
            )
            a_spec = self._sharding.spec  # whole lattices per device
            b_spec = P(a_spec[0], None, None)
            self._steps[k] = jax.jit(
                site_local(jax.vmap(raw), self.mesh, (a_spec, b_spec), a_spec),
                out_shardings=self._sharding,
            )
        return self._steps[k]

    def batch_sharding(self, bsz: int) -> NamedSharding:
        """Placement of a ``bsz``-lattice physical batch: whole lattices per
        device when ``bsz`` divides the mesh, else each lattice site-sharded."""
        if bsz % self.n_devices == 0:
            return self._sharding
        return NamedSharding(self.mesh, P(None, *self.plan.sharding.spec))

    def pack_batch(self, a: jax.Array) -> jax.Array:
        """Canonical (B, n_sites, 4, 3, 3) complex -> batched physical form,
        packed on the host and placed by :meth:`batch_sharding`."""
        return jax.device_put(self._pack_on_host(a), self.batch_sharding(a.shape[0]))

    def _pack_on_host(self, a: jax.Array) -> jax.Array:
        """Canonical (B, n_sites, 4, 3, 3) complex -> batched physical form,
        zero-padded to the plan's sites, on the host."""
        if a.shape[1] > self.plan.padded_sites:
            raise ValueError(
                f"batch carries {a.shape[1]} sites > plan capacity "
                f"{self.plan.padded_sites} (L={self.cfg.L}, tile={self.cfg.tile})"
            )

        def pack(a: jax.Array) -> jax.Array:
            pad = self.plan.padded_sites - a.shape[1]
            if pad:
                a = jnp.concatenate(
                    [a, jnp.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)], axis=1
                )
            return jax.vmap(self.plan.codec.pack)(a)

        return layouts.on_host(pack, a)

    def pack_vec_batch(self, v: jax.Array) -> jax.Array:
        """Canonical (B, n_sites, 3) vector fields -> planar (B, 2, 3, S),
        packed on the host and placed by :meth:`batch_sharding`."""
        codec, n = self.plan.codec, self.plan.padded_sites
        v_p = layouts.on_host(jax.vmap(lambda x: codec.pack_vec(x, n)), v)
        return jax.device_put(v_p, self.batch_sharding(v.shape[0]))

    def unpack_batch(self, c_phys: jax.Array, n_sites: int | None = None) -> jax.Array:
        """Batched physical -> canonical complex on the host."""
        n = n_sites if n_sites is not None else self.cfg.shape.n_sites
        return layouts.on_host(
            jax.vmap(lambda x: self.plan.codec.unpack(x, n)), c_phys
        )

    def run(self, a_batch: jax.Array, b_batch: jax.Array, k: int = 1) -> jax.Array:
        """Batched physical (B, ...) x planar B (B, 2, 36) -> physical C batch."""
        bsz = a_batch.shape[0]
        pad = (-bsz) % self.n_devices
        if pad:
            zeros = lambda x: jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
            )
            a_batch, b_batch = zeros(a_batch), zeros(b_batch)
        c = self._batched_step(k)(a_batch, b_batch)
        return c[:bsz] if pad else c

    def _whole_lattices(self, bsz: int, ndim: int) -> NamedSharding:
        """Placement of a rank-``ndim`` batch of ``bsz`` lattices' words:
        whole lattices per device when ``bsz`` divides the mesh, else every
        device holds the whole batch."""
        if bsz % self.n_devices:
            return NamedSharding(self.mesh, P())
        return NamedSharding(
            self.mesh, P(self._sharding.spec[0], *(None,) * (ndim - 1)))

    def _relayout(self, fn: Callable[[jax.Array], jax.Array], x: jax.Array,
                  out_ndim: int) -> jax.Array:
        """A relayout kernel ``fn`` over a batch, run where
        :meth:`_whole_lattices` places it (a Pallas call on sharded operands
        runs inside a shard_map)."""
        bsz = x.shape[0]
        return site_local(fn, self.mesh, self._whole_lattices(bsz, x.ndim).spec,
                          self._whole_lattices(bsz, out_ndim).spec)(x)

    def _device_pack(self, words: tuple[int, ...], links: tuple[int, ...]):
        """Jitted ``(A words, B words) -> (A physical, B planar)`` on the
        devices, for the words of A's ``padded_sites`` sites, shaped
        ``words`` (see :func:`~repro.core.su3.layouts.words_view`), and B's
        ``(B, 72)``.

        The same data movement and cast as :meth:`_pack_on_host` and
        ``pack_links_on_host``, bit for bit.  No array with the canonical
        minor dimensions of 3 or 4 is made: the planar layouts go through
        the relayout kernel, AoS through its own (S, 72) words."""
        key = ("pack", words, links)
        if key not in self._codecs:
            codec, s_pad = self.plan.codec, self.plan.padded_sites
            bsz, s_k = words[0], _relayout_sites(s_pad)

            def pack(x: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
                if codec.layout == Layout.AOS:
                    w = x.reshape(bsz, s_pad, layouts.GAUGE_WORDS)
                    a = jax.vmap(layouts.aos_from_words)(w).astype(codec.word_dtype)
                else:
                    x = x.reshape(bsz, -1)  # a no-op on lane-dense words
                    x = jnp.pad(x, ((0, 0), (0, s_k * layouts.GAUGE_WORDS - x.shape[1])))
                    p = self._relayout(_kops.planar_from_flat,
                                       x.reshape(bsz, -1, layouts.LANE), 4)
                    a = jax.vmap(codec.pack_planar)(p[..., :s_pad])
                b = jnp.swapaxes(b.reshape(-1, layouts.PLANAR_ROWS, 2), 1, 2)
                return a, b.astype(codec.word_dtype)

            self._codecs[key] = jax.jit(
                pack, out_shardings=(self.batch_sharding(bsz), self.plan.replicated))
        return self._codecs[key]

    def _device_unpack(self, phys: tuple[int, ...]):
        """Jitted physical batch of shape ``phys`` -> the canonical float32
        words of its ``padded_sites`` sites, flat and after a
        ``FETCH_HEAD``-word head: the inverse of :meth:`_device_pack`, with
        the values of :meth:`unpack_batch` (TWO_ROW's rebuilt third row
        within rounding)."""
        key = ("unpack", phys)
        if key not in self._codecs:
            codec, s_pad = self.plan.codec, self.plan.padded_sites
            bsz, s_k = phys[0], _relayout_sites(s_pad)
            n_words = s_pad * layouts.GAUGE_WORDS

            def unpack(c: jax.Array) -> jax.Array:
                if codec.layout == Layout.AOS:
                    w = c[..., : layouts.GAUGE_WORDS].astype(jnp.float32)
                else:
                    p = jax.vmap(codec.unpack_planar)(c)
                    p = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (0, s_k - s_pad)))
                    w = self._relayout(_kops.flat_from_planar, p, 3)
                w = w.reshape(bsz, -1)[:, :n_words].reshape(-1)
                return jnp.concatenate([jnp.zeros((FETCH_HEAD,), w.dtype), w])

            self._codecs[key] = jax.jit(unpack, out_shardings=self.plan.replicated)
        return self._codecs[key]

    def multiply(self, a: jax.Array, b: jax.Array, k: int = 1) -> jax.Array:
        """Canonical batched entry: a (B, S, 4, 3, 3), b (B, 4, 3, 3) complex.

        Five steps, each a span on :attr:`tracer`: ``transfer.h2d``
        (``bytes`` placed on the devices: A's and B's canonical words,
        viewed as float32 without a copy), ``codec.pack`` (``on="device"``:
        the words relaid into the plan's physical form), ``device.step``
        (the batched chain of ``k``), ``codec.unpack`` (``on="device"``:
        back to canonical words) and ``transfer.d2h`` (``bytes`` fetched;
        the host reads them as complex, with no copy where the CPU array
        can alias the fetched buffer, see :data:`FETCH_HEAD`).  A lattice
        smaller than the plan travels with its sites zero-padded to the
        plan's, as the physical form did.  A recording tracer waits for each step's
        work before closing its span; an idle one waits for nothing, and the
        caller waits on the result.
        """
        tr = self.tracer
        done = jax.block_until_ready if tr.enabled else (lambda x: x)
        bsz, n_sites = a.shape[:2]
        s_pad = self.plan.padded_sites
        if n_sites > s_pad:
            raise ValueError(
                f"batch carries {n_sites} sites > plan capacity "
                f"{s_pad} (L={self.cfg.L}, tile={self.cfg.tile})"
            )
        a = np.asarray(a)
        if n_sites < s_pad:
            a = np.concatenate(
                [a, np.zeros((bsz, s_pad - n_sites) + a.shape[2:], a.dtype)], axis=1)
        words, links = layouts.words_view(a), layouts.words_view(np.asarray(b))
        # each step rebinds ``x``, so no step's input outlives it on the device
        with tr.span("transfer.h2d") as span:
            x, b_p = done(jax.device_put(
                (words, links),
                (self._whole_lattices(bsz, words.ndim), self.plan.replicated)))
            span.set(bytes=x.nbytes + b_p.nbytes)
        with tr.span("codec.pack", on="device"):
            x, b_p = done(self._device_pack(words.shape, links.shape)(x, b_p))
        with tr.span("device.step", k=k):
            x = done(self.run(x, b_p, k=k))
        with tr.span("codec.unpack", on="device"):
            x = done(self._device_unpack(x.shape)(x))
        with tr.span("transfer.d2h") as span:
            c = np.asarray(x)[FETCH_HEAD:].view(np.complex64).reshape(a.shape)
            span.set(bytes=c.nbytes)
            return jax.device_put(c[:, :n_sites], jax.devices("cpu")[0])

