"""Lattice data layouts for the SU3 kernel.

The paper's central Xeon lesson is that the *physical layout* of the ``site``
struct determines achievable bandwidth:

  * the original MILC-derived AoS ``site`` struct is 320 B (fp32) per site, of
    which only 288 B (4 links x 72 B) are the gauge field — the x/y/z/t/index/
    parity/pad words are dead weight that (a) inflates streamed traffic by
    320/288 = 1.11x and (b) leaves gaps that defeat streaming stores;
  * ``B`` is accessed column-major (non-unit stride) and is better transposed
    into a thread-local copy.

On TPU the analogous axes are VPU lanes (128-wide) and VMEM tiles:

  * ``AOS``       — faithful paper layout: (n_sites, 80) fp32 words per site
                    (72 gauge + 8 metadata/pad). Charged in the traffic model.
  * ``SOA``       — planar structure-of-arrays: (2, 4, 3, 3, n_sites); complex
                    split re/im (TPU has no complex MXU/VPU path), site index
                    innermost → unit-stride lane vectors, no padding traffic.
  * ``AOSOA``     — site-tiled SoA: (n_tiles, 2, 4, 3, 3, lane) with lane=128;
                    one tile is one VPU-lane-aligned working set. This is the
                    paper's "blocked GEMM fits the register file" re-derived
                    for the HBM→VMEM→VREG hierarchy.

Canonical (logical) form everywhere else in the library is complex:
  A : (n_sites, 4, 3, 3) complex   B : (4, 3, 3) complex.
Canonical arrays live on the host (:func:`on_host`); only physical forms, and
the canonical float32 words (:func:`words_view`), which a TPU holds without
padding, go onto an accelerator.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

LINKS = 4  # links per site (the j loop)
SU3 = 3  # SU(3) matrix dimension
GAUGE_WORDS = LINKS * SU3 * SU3 * 2  # 72 real words of gauge field per site
SITE_PAD_WORDS = 8  # x, y, z, t, index, parity(+align), pad[2]  (PRECISION==1)
SITE_WORDS_AOS = GAUGE_WORDS + SITE_PAD_WORDS  # 80 words = 320 B fp32, paper-faithful
LANE = 128  # TPU VPU lane width


class Layout(str, enum.Enum):
    AOS = "aos"
    SOA = "soa"
    AOSOA = "aosoa"


class GaugeCompression(str, enum.Enum):
    """How many rows of each SU(3) link the physical form stores.

    ``TWO_ROW`` is the staggered-Dslash-on-KNL trick (arXiv:1411.2087): an
    SU(3) matrix is determined by its first two rows — the third is the
    unitarity cross product ``row2 = conj(row0 x row1)`` — so storage drops
    from 18 to 12 reals per link (72 -> 48 words per site) and the consumer
    reconstructs row 2 in registers.  Exact only on SU(3); for arbitrary
    matrices the reconstruction error is bounded by the distance to the
    nearest unitary (the codec round-trip property tests pin this).
    """

    NONE = "none"
    TWO_ROW = "two_row"


@dataclasses.dataclass(frozen=True)
class LatticeShape:
    """Lattice of dimension L^4, matching the paper's ``total_sites = L**4``."""

    L: int

    @property
    def n_sites(self) -> int:
        return self.L**4

    def padded_sites(self, lane: int = LANE) -> int:
        return ((self.n_sites + lane - 1) // lane) * lane


def parity_sites(L: int) -> tuple[np.ndarray, np.ndarray]:
    """Site ids of the even and of the odd sites, each in increasing order.

    Sites are t-major, ``((t*L + z)*L + y)*L + x``, and a site's parity is
    ``(x + y + z + t) % 2``.  A half-lattice (even/odd) vector is the
    canonical ``(L**4 // 2, 3)`` field of one parity in this order; its
    planar form is the codec's ``pack_vec`` of it.  L must be even, or the
    periodic lattice is not bipartite.
    """
    if L % 2:
        raise ValueError(f"an even/odd split needs an even L, got L={L}")
    s = np.arange(L**4, dtype=np.int64)
    parity = (s % L + s // L % L + s // L**2 % L + s // L**3) % 2
    return np.flatnonzero(parity == 0), np.flatnonzero(parity == 1)


# ---------------------------------------------------------------------------
# Canonical <-> physical layout converters.
# ---------------------------------------------------------------------------


def on_host(fn: Callable[..., Any], *args: Any) -> Any:
    """``fn(*args)`` computed on the host CPU, its results committed there.

    Canonical complex arrays have minor dimensions of 3 and 4, which a TPU
    pads to 128 lanes: an L=32 gauge field of 288 MiB takes 9 GB of HBM.  So
    canonical arrays never go onto an accelerator: array arguments are
    fetched to the host first, and callers ``device_put`` the physical
    results where the plan shards them.  On a CPU backend the host is the
    device, and only arrays sharded over several CPU devices are gathered.
    """
    cpu = jax.devices("cpu")[0]

    def put(x: Any) -> Any:
        return jax.device_put(x, cpu) if isinstance(x, (jax.Array, np.ndarray)) else x

    with jax.default_device(cpu):
        return jax.tree.map(put, fn(*map(put, args)))


def _real_dtype(complex_dtype: Any) -> Any:
    return jnp.float64 if complex_dtype == jnp.complex128 else jnp.float32


def to_planar(a: jax.Array) -> jax.Array:
    """complex (..., ) -> stacked planar (2, ...) real array (re, im)."""
    return jnp.stack([jnp.real(a), jnp.imag(a)], axis=0)


def from_planar(p: jax.Array) -> jax.Array:
    return jax.lax.complex(p[0], p[1])


def pack_aos(a: jax.Array, site_meta: jax.Array | None = None) -> jax.Array:
    """Canonical A (n_sites, 4, 3, 3) complex -> paper-faithful AoS (n_sites, 80).

    Words [0:72] are interleaved (re, im) gauge entries in link-major order —
    exactly MILC's ``site.link[4]``; words [72:80] are the metadata/pad block.
    """
    n_sites = a.shape[0]
    gauge = jnp.stack([jnp.real(a), jnp.imag(a)], axis=-1)  # (s, 4, 3, 3, 2)
    return aos_from_words(
        gauge.reshape(n_sites, GAUGE_WORDS).astype(_real_dtype(a.dtype)), site_meta)


def aos_from_words(gauge: jax.Array, site_meta: jax.Array | None = None) -> jax.Array:
    """Canonical gauge words (n_sites, 72) real -> AoS (n_sites, 80) at the
    words' dtype, the metadata/pad block appended (see :func:`pack_aos`)."""
    n_sites, dt = gauge.shape[0], gauge.dtype
    if site_meta is None:
        # x, y, z, t, index, parity, pad, pad — populated like the benchmark's
        # make_lattice(): index = linear site id; coords from L is unknown here
        # so carry the linear index in all coordinate words (metadata is dead
        # weight for the kernel either way; that is the point of this layout).
        idx = jnp.arange(n_sites, dtype=dt)[:, None]
        site_meta = jnp.concatenate(
            [idx, idx, idx, idx, idx, idx % 2, jnp.zeros((n_sites, 2), dt)], axis=1
        )
    return jnp.concatenate([gauge, site_meta.astype(dt)], axis=1)


def unpack_aos(aos: jax.Array, complex_dtype: Any = jnp.complex64) -> jax.Array:
    n_sites = aos.shape[0]
    gauge = aos[:, :GAUGE_WORDS].reshape(n_sites, LINKS, SU3, SU3, 2)
    return jax.lax.complex(gauge[..., 0], gauge[..., 1]).astype(complex_dtype)


def pack_soa(a: jax.Array) -> jax.Array:
    """Canonical (n_sites, 4, 3, 3) complex -> SoA planar (2, 4, 3, 3, n_sites)."""
    return to_planar(jnp.moveaxis(a, 0, -1))


def unpack_soa(soa: jax.Array, complex_dtype: Any = jnp.complex64) -> jax.Array:
    return jnp.moveaxis(from_planar(soa), -1, 0).astype(complex_dtype)


def pack_aosoa(a: jax.Array, lane: int = LANE) -> jax.Array:
    """Canonical -> (n_tiles, 2, 4, 3, 3, lane). Pads site count up to lane."""
    n_sites = a.shape[0]
    pad = (-n_sites) % lane
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)
    n_tiles = a.shape[0] // lane
    # (tiles, lane, 4, 3, 3) -> (tiles, 4, 3, 3, lane) -> planar
    t = jnp.moveaxis(a.reshape(n_tiles, lane, LINKS, SU3, SU3), 1, -1)
    return jnp.stack([jnp.real(t), jnp.imag(t)], axis=1)


def unpack_aosoa(
    t: jax.Array, n_sites: int, complex_dtype: Any = jnp.complex64
) -> jax.Array:
    c = jax.lax.complex(t[:, 0], t[:, 1])  # (tiles, 4, 3, 3, lane)
    c = jnp.moveaxis(c, -1, 1).reshape(-1, LINKS, SU3, SU3)
    return c[:n_sites].astype(complex_dtype)


# ---------------------------------------------------------------------------
# LayoutCodec — pack/unpack/shard as a first-class object.
#
# Historically the engine re-derived the canonical<->physical conversion (and
# its padded twin) per layout in three separate if/elif chains; the codec is
# the single owner of that logic.  A codec knows:
#   * the physical array produced from canonical complex (S, 4, 3, 3) data,
#   * how to restore canonical data (optionally sliced to the live sites),
#   * the PartitionSpec that shards the physical form over a 1-D site mesh,
#   * the planar "kernel view" (2, 36, S) the Pallas path consumes.
# ---------------------------------------------------------------------------

PLANAR_ROWS = LINKS * SU3 * SU3  # 36 complex entries per site

# Two-row compressed planar form: 4 links x 2 stored rows x 3 cols = 24
# complex entries per site (48 real words).  Row order is the full form's
# with every k=2 row deleted, so COMP_ROW_INDICES gathers the compressed
# rows out of a full 36-row planar array (and is the store-side "drop row
# 2" map the kernels use).
PLANAR_COMP_ROWS = LINKS * 2 * SU3  # 24
GAUGE_COMP_WORDS = PLANAR_COMP_ROWS * 2  # 48 real words per site
COMP_ROW_INDICES = tuple(
    (j * SU3 + k) * SU3 + l
    for j in range(LINKS)
    for k in range(2)
    for l in range(SU3)
)


def reconstruct_third_row(r0: jax.Array, r1: jax.Array) -> jax.Array:
    """row2 = conj(row0 x row1) — the SU(3) unitarity reconstruction.

    ``r0``/``r1`` are complex arrays with the color index last (..., 3).
    Expanded in *real* arithmetic with the exact operand grouping of the
    kernels' in-register reconstruction (``su3_matmul._expand_tile``), NOT
    via complex primitives — same formula, same f32 precision; values agree
    with the in-kernel reconstruction to ~1 ulp (LLVM FMA contraction can
    round mul+add pairs differently across compiled programs, so bitwise
    equality across *different* programs is not guaranteed — see
    ``_expand_tile`` for what is exactly pinned).  Computed at the input
    precision; callers wanting f32 reconstruction from narrower storage
    upcast first.
    """
    re, im = conj_cross(jnp.real(r0), jnp.imag(r0), jnp.real(r1), jnp.imag(r1), -1)
    return jax.lax.complex(re, im)


def conj_cross(
    a_r: jax.Array, a_i: jax.Array, b_r: jax.Array, b_i: jax.Array, axis: int
) -> tuple[jax.Array, jax.Array]:
    """(re, im) of conj(a x b) for color 3-vectors given by their real and
    imaginary parts, the color index on ``axis`` — the arithmetic of
    :func:`reconstruct_third_row` on real arrays."""

    def c(x: jax.Array, i: int) -> jax.Array:
        return jax.lax.index_in_dim(x, i, axis, keepdims=False)

    def _comp(i: int, j: int) -> tuple[jax.Array, jax.Array]:
        # conj(a[i]*b[j] - a[j]*b[i]), grouped as in _expand_tile
        xr = (c(a_r, i) * c(b_r, j) - c(a_i, i) * c(b_i, j)) - (
            c(a_r, j) * c(b_r, i) - c(a_i, j) * c(b_i, i)
        )
        xi = (c(a_r, i) * c(b_i, j) + c(a_i, i) * c(b_r, j)) - (
            c(a_r, j) * c(b_i, i) + c(a_i, j) * c(b_r, i)
        )
        return xr, -xi

    comps = [_comp(1, 2), _comp(2, 0), _comp(0, 1)]
    return (jnp.stack([re for re, _ in comps], axis=axis),
            jnp.stack([im for _, im in comps], axis=axis))


def words_view(a: np.ndarray) -> np.ndarray:
    """Canonical complex64 ``(B, S, 4, 3, 3)`` on the host as its float32
    words, without a copy: ``(B, S·72/128, 128)`` when ``S·72`` is a
    multiple of 128 (every even L), else ``(B, S·72)``.

    The 3-D view is lane-dense: a TPU tiles it with no padding and its tiled
    bytes are its row-major bytes, so it goes onto an accelerator as one
    flat copy (unlike the canonical array, see :func:`on_host`).
    """
    w = np.ascontiguousarray(a, np.complex64).view(np.float32).reshape(a.shape[0], -1)
    return w.reshape(a.shape[0], -1, LANE) if w.shape[1] % LANE == 0 else w


@dataclasses.dataclass(frozen=True)
class LayoutCodec:
    """Canonical <-> physical converter for one (layout, tile, word dtype).

    ``tile`` is the AoSoA lane width / Pallas site-tile; AOS and SOA ignore it
    for shape purposes but carry it so a codec fully identifies the physical
    form used by an :class:`repro.core.su3.plan.ExecutionPlan`.

    ``accum_dtype`` ("" = same as ``dtype``) records the *compute* width of
    mixed-precision plans: storage words stream at ``dtype`` (what pack emits
    and the traffic model charges) while the kernel accumulates at
    ``accum_dtype`` — the bf16-storage / f32-accumulate serving scheme.

    ``compression`` selects the stored-row set of each link.  TWO_ROW keeps
    rows 0 and 1 only (24 planar rows instead of 36); the codec itself never
    materializes row 2 in the physical array — ``pack`` drops it, kernels
    reconstruct it in registers, and only ``unpack`` (the canonical escape
    hatch) rebuilds it, in f32, via :func:`reconstruct_third_row`.
    """

    layout: Layout
    tile: int = LANE
    dtype: str = "float32"
    accum_dtype: str = ""  # "" => accumulate at the storage dtype
    compression: GaugeCompression = GaugeCompression.NONE

    @property
    def word_dtype(self) -> Any:
        return jnp.dtype(self.dtype)

    @property
    def is_compressed(self) -> bool:
        return self.compression == GaugeCompression.TWO_ROW

    @property
    def planar_rows(self) -> int:
        """Planar gauge rows of the physical form: 36 full, 24 two-row."""
        return PLANAR_COMP_ROWS if self.is_compressed else PLANAR_ROWS

    @property
    def stored_rows(self) -> int:
        """SU(3) matrix rows present in storage (3 full, 2 compressed)."""
        return 2 if self.is_compressed else SU3

    @property
    def compute_dtype(self) -> str:
        """The dtype FMAs run at: accum_dtype when set, else the word dtype."""
        return self.accum_dtype or self.dtype

    @property
    def is_mixed_precision(self) -> bool:
        return bool(self.accum_dtype) and self.accum_dtype != self.dtype

    # -- canonical <-> physical ------------------------------------------------

    def pack(self, a: jax.Array) -> jax.Array:
        """Canonical complex (n_sites, 4, 3, 3) -> physical layout array.

        TWO_ROW drops each link's third row before laying out — the stored
        form is (2, 24, S) / (tiles, 2, 24, lane); row 2 never exists
        physically.
        """
        if self.layout == Layout.AOS:
            return pack_aos(a).astype(self.word_dtype)  # (S, 80)
        return self.pack_planar(
            to_planar(jnp.moveaxis(a, 0, -1)).reshape(2, PLANAR_ROWS, -1))

    def pack_planar(self, p: jax.Array) -> jax.Array:
        """Planar (2, 36, n_sites) real, every row -> the physical form of a
        planar-view layout, in the word dtype: TWO_ROW keeps rows 0 and 1 of
        each link, AoSoA pads the sites to the tile and goes tile-major."""
        if self.is_compressed:
            p = p.reshape(2, LINKS, SU3 * SU3, -1)[:, :, : 2 * SU3]
            p = p.reshape(2, PLANAR_COMP_ROWS, -1)
        if self.layout == Layout.SOA:
            return p.astype(self.word_dtype)
        if self.layout != Layout.AOSOA:
            raise ValueError(f"{self.layout} has no planar form")
        pad = (-p.shape[-1]) % self.tile
        if pad:
            p = jnp.pad(p, ((0, 0), (0, 0), (0, pad)))
        t = p.reshape(2, self.planar_rows, -1, self.tile)
        return jnp.moveaxis(t, 2, 0).astype(self.word_dtype)

    def unpack_planar(self, phys: jax.Array) -> jax.Array:
        """Physical form of a planar-view layout -> planar (2, 36, S) float32,
        every row: TWO_ROW's third row is rebuilt with the arithmetic of
        :meth:`unpack`."""
        p = self.planar_view(phys).astype(jnp.float32)
        if not self.is_compressed:
            return p
        t = p.reshape(2, LINKS, 2 * SU3, -1)  # rows 0 and 1 of each link
        r2 = conj_cross(t[0, :, :SU3], t[1, :, :SU3], t[0, :, SU3:], t[1, :, SU3:], 1)
        return jnp.concatenate([t, jnp.stack(r2)], axis=2).reshape(2, PLANAR_ROWS, -1)

    def unpack(self, phys: jax.Array, n_sites: int | None = None) -> jax.Array:
        """Physical -> canonical complex; slice to ``n_sites`` when given.

        For TWO_ROW storage the third row is reconstructed here, in f32, via
        the unitarity cross product — bit-identical to what the kernels
        rebuild in registers (same formula, same precision).
        """
        f32 = phys.astype(jnp.float32)
        sr = self.stored_rows
        if self.layout == Layout.AOS:
            c = unpack_aos(f32)
        elif self.layout == Layout.SOA:
            c = unpack_soa(f32.reshape(2, LINKS, sr, SU3, -1))
        else:
            t = f32.reshape(phys.shape[0], 2, LINKS, sr, SU3, self.tile)
            cc = jax.lax.complex(t[:, 0], t[:, 1])  # (tiles, 4, sr, 3, lane)
            cc = jnp.moveaxis(cc, -1, 1).reshape(-1, LINKS, sr, SU3)
            c = cc.astype(jnp.complex64)
        if self.is_compressed:
            r2 = reconstruct_third_row(c[:, :, 0, :], c[:, :, 1, :])
            c = jnp.concatenate([c, r2[:, :, None, :]], axis=2)
        return c if n_sites is None else c[:n_sites]

    def pack_b(self, b: jax.Array) -> jax.Array:
        """Canonical B (4, 3, 3) complex -> planar (2, 36) in the word dtype."""
        return to_planar(b).reshape(2, PLANAR_ROWS).astype(self.word_dtype)

    def unpack_b(self, b_p: jax.Array) -> jax.Array:
        return from_planar(b_p.astype(jnp.float32).reshape(2, LINKS, SU3, SU3))

    # -- color-vector fields (the stencil workload's v) ------------------------
    #
    # The vector field is planar (2, 3, S) in every layout — it has no AoS
    # metadata and no per-layout physical form; only the word dtype (and the
    # site padding the caller applies) varies.  Site order matches the
    # lattice's linear site ids, i.e. the planar view's site axis.

    def pack_vec(self, v: jax.Array, padded_sites: int | None = None) -> jax.Array:
        """Canonical vector field (n_sites, 3) complex -> planar (2, 3, S)
        in the word dtype, zero-padded to ``padded_sites`` when given."""
        p = to_planar(jnp.moveaxis(v, 0, -1))  # (2, 3, n_sites)
        if padded_sites is not None and padded_sites > v.shape[0]:
            p = jnp.pad(p, ((0, 0), (0, 0), (0, padded_sites - v.shape[0])))
        return p.astype(self.word_dtype)

    def unpack_vec(self, v_p: jax.Array, n_sites: int | None = None) -> jax.Array:
        """Planar (2, 3, S) -> canonical complex (n_sites, 3)."""
        c = jnp.moveaxis(from_planar(v_p.astype(jnp.float32)), -1, 0)
        return c if n_sites is None else c[:n_sites]

    # -- sharding --------------------------------------------------------------

    def site_spec(
        self, site_axes: tuple[str, ...] = ("sites",)
    ) -> "jax.sharding.PartitionSpec":
        """PartitionSpec sharding the physical site axis over ``site_axes``.

        Args:
            site_axes: mesh axis names the site dimension shards over, major
                first — ``("sites",)`` on the legacy 1-D mesh,
                ``("hosts", "devices")`` on a (host, device) mesh (see
                ``repro.distributed.sharding.lattice_site_axes``).

        Returns:
            The layout's PartitionSpec with every non-site dimension
            replicated: ``(sites, 80)`` for AOS, ``(2, 36, S)`` for SOA
            (site axis last), ``(tiles, 2, 36, lane)`` for AoSoA (the tile
            axis is the site axis).
        """
        P = jax.sharding.PartitionSpec
        ax = site_axes if len(site_axes) > 1 else site_axes[0]
        if self.layout == Layout.AOS:
            return P(ax, None)  # (sites, 80)
        if self.layout == Layout.SOA:
            return P(None, None, ax)  # (2, 36, S)
        return P(ax, None, None, None)  # (tiles, 2, 36, lane)

    # -- the Pallas kernel's planar view --------------------------------------

    @property
    def supports_planar_view(self) -> bool:
        return self.layout in (Layout.SOA, Layout.AOSOA)

    def planar_view(self, phys: jax.Array) -> jax.Array:
        """Physical -> flattened planar (2, 36, S) without changing dtype.

        Tile-major site order (s = tile_idx * lane + lane_idx), the exact
        inverse of :meth:`from_planar_view` and consistent with
        ``pack_aosoa``'s site numbering.  (The pre-codec engine used a
        lane-major flatten here with a tile-major unflatten — a site
        permutation masked by the benchmark's uniform lattice data.)
        """
        if self.layout == Layout.SOA:
            return phys
        if self.layout == Layout.AOSOA:
            return jnp.moveaxis(phys, 0, 2).reshape(2, self.planar_rows, -1)
        raise ValueError(f"{self.layout} has no planar kernel view")

    def from_planar_view(self, c_p: jax.Array, like: jax.Array) -> jax.Array:
        """Planar (2, rows, S) -> physical, shaped like ``like``."""
        if self.layout == Layout.SOA:
            return c_p
        if self.layout == Layout.AOSOA:
            c_t = c_p.reshape(2, self.planar_rows, like.shape[0], self.tile)
            return jnp.moveaxis(c_t, 2, 0)
        raise ValueError(f"{self.layout} has no planar kernel view")


def make_codec(
    layout: Layout,
    tile: int = LANE,
    dtype: str = "float32",
    accum_dtype: str = "",
    compression: GaugeCompression | str = GaugeCompression.NONE,
) -> LayoutCodec:
    """The one construction site for layout codecs."""
    comp = GaugeCompression(compression)
    if comp != GaugeCompression.NONE and Layout(layout) == Layout.AOS:
        # The AoS layout exists to reproduce the paper's 320 B site struct
        # verbatim; a compressed variant of it is not a form the paper (or
        # any kernel here) defines.
        raise ValueError("gauge compression is only defined for SOA/AoSoA layouts")
    return LayoutCodec(
        layout=Layout(layout),
        tile=tile,
        dtype=dtype,
        accum_dtype=accum_dtype,
        compression=comp,
    )


# ---------------------------------------------------------------------------
# Traffic model — charges each layout the bytes it actually streams.
# This is the quantitative form of the paper's 288/320 streaming-store point.
# ---------------------------------------------------------------------------


WORD_BYTES = {"float32": 4, "bfloat16": 2, "float64": 8}


@dataclasses.dataclass(frozen=True)
class TrafficModel:
    """Bytes moved per kernel invocation for a given layout/dtype.

    read(A) + write(C); B is cache/VMEM-resident after first read (paper §3.1:
    "B could stay in the cache and can be reused") and charged once, which is
    negligible, so it is excluded exactly as in the paper's AI computation.

    Mixed-precision plans are charged at *storage* width: a bf16-storage /
    f32-accumulate plan streams 2-byte words over HBM (the accumulate happens
    on the VMEM-resident tile and never hits memory), so ``word_bytes`` is
    always the storage dtype's width.
    """

    layout: Layout
    n_sites: int
    word_bytes: int  # 4 for fp32, 2 for bf16, 8 for fp64 — STORAGE width
    compression: GaugeCompression = GaugeCompression.NONE

    @classmethod
    def for_dtype(
        cls,
        layout: Layout,
        n_sites: int,
        dtype: str,
        compression: GaugeCompression | str = GaugeCompression.NONE,
    ) -> "TrafficModel":
        return cls(layout, n_sites, WORD_BYTES[dtype], GaugeCompression(compression))

    @property
    def words_per_site(self) -> int:
        if self.layout == Layout.AOS:
            return SITE_WORDS_AOS  # 80: pads are streamed too
        if self.compression == GaugeCompression.TWO_ROW:
            return GAUGE_COMP_WORDS  # 48: two stored rows per link
        return GAUGE_WORDS  # 72: SoA/AoSoA carry no metadata

    @property
    def bytes_per_site_rw(self) -> int:
        return 2 * self.words_per_site * self.word_bytes  # read A + write C

    @property
    def total_bytes(self) -> int:
        return self.n_sites * self.bytes_per_site_rw

    @property
    def flops_per_site(self) -> int:
        # 4 links x (3x3x3 complex MACs) x (4 mul + 4 add) = 864 (paper §3.1)
        return LINKS * SU3 * SU3 * SU3 * 8

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops_per_site / self.bytes_per_site_rw


def paper_arithmetic_intensity(word_bytes: int = 4) -> float:
    """AI = 864 / (320 * 2) = 1.35 fp32 / 0.675 fp64 — paper §3.1 exactly."""
    return TrafficModel(Layout.AOS, 1, word_bytes).arithmetic_intensity
