"""Unified SU3 kernel registry.

Before the ExecutionPlan refactor the repo had *two* kernel namespaces: the
XLA variant table (``variants._REGISTRY``) and the hardcoded ``"pallas"``
string special-cased by the engine's step builder.  Both now live here, with
enough metadata for a plan to validate and wire a kernel without per-kernel
``if/elif`` chains:

  ``form``
      ``"canonical"`` — fn(a, b) on canonical complex arrays
      (a: (S, 4, 3, 3), b: (4, 3, 3)); the plan wraps it with the layout
      codec's unpack/pack.
      ``"planar"`` — fn(a_p, b_p, *, tile, k_iters, interpret) on the
      flattened planar view (a_p: (2, 36, S), b_p: (2, 36)); the plan feeds
      it the codec's planar view directly (zero-copy for SoA).
      ``"stencil"`` — fn(u_p, v_nbr, *, tile, interpret, accum_dtype?) on the
      planar link view plus direction-major shifted neighbor vectors
      (u_p: (2, 36, S), v_nbr: (8, 2, 3, S) -> out (2, 3, S)); the
      nearest-neighbor Dslash-style operator.  Consumed only by
      ``ExecutionPlan.stencil_step`` / ``stencil_reference_step`` — a
      stencil kernel cannot serve as a plan's multiply ``step``.
      ``"stencil_axpy"`` — fn(u_p, r_nbr, p_nbr, r_p, p_p, coefs, *, tile,
      interpret, accum_dtype?) -> (p_new, s): one fused conjugate-gradient
      iteration body — the search-direction axpy ``p' = r + beta p`` formed
      on the resident neighbor tiles plus the raw stencil apply ``S(p')``
      in the same pallas_call.  The sigma shift ``ap = sigma p' + S(p')``
      runs in the plan's shared epilogue program (bit-identity contract).
      Consumed only by ``ExecutionPlan.cg_solve`` / ``cg_iterate``.
  ``layouts``
      which physical layouts the kernel can be planned with.
  ``backends``
      ``"xla"`` | ``"pallas"`` — what lowers the kernel body.
  ``supports_fused``
      whether fn accepts ``k_iters`` and chains K multiplies in one dispatch.
  ``supports_accum``
      whether fn accepts ``accum_dtype`` and can accumulate at a wider
      precision than the storage words it streams (bf16-storage/f32-accumulate
      plans).  Canonical-form kernels get this for free — the layout codec
      unpacks to float32 complex before they run — so the flag only gates the
      planar path, where the kernel itself owns the upcast.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

from repro.core.su3.layouts import Layout

CANONICAL = "canonical"
PLANAR = "planar"
STENCIL = "stencil"
STENCIL_AXPY = "stencil_axpy"


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One registered SU3 kernel plus the metadata a plan validates against.

    Attributes:
        name: registry key (``EngineConfig.variant``).
        fn: the kernel callable.  Canonical form:
            ``fn(a, b) -> c`` with ``a/c: (S, 4, 3, 3)`` complex64 and
            ``b: (4, 3, 3)`` complex64.  Planar form:
            ``fn(a_p, b_p, *, tile, k_iters, alias, interpret?,
            accum_dtype?) -> c_p`` with ``a_p/c_p: (2, 36, S)`` and
            ``b_p: (2, 36)`` real words in the storage dtype.
        layouts: physical layouts the kernel can be planned with.
        backends: ``"xla"`` / ``"pallas"`` — what lowers the body.
        form: ``"canonical"`` or ``"planar"`` (module constants).
        supports_fused: fn accepts ``k_iters`` and chains K multiplies in
            one dispatch.
        supports_accum: fn accepts ``accum_dtype`` (planar mixed-precision).
        supports_compressed: fn accepts ``compressed`` and can stream two-row
            (24-planar-row) gauge blocks, reconstructing row 2 in-register.
    """

    name: str
    fn: Callable
    layouts: tuple[Layout, ...]
    backends: tuple[str, ...]
    form: str = CANONICAL
    supports_fused: bool = False
    supports_accum: bool = False
    supports_compressed: bool = False

    def supports_layout(self, layout: Layout) -> bool:
        """Whether this kernel can be planned with ``layout`` (accepts the
        enum or its string value)."""
        return Layout(layout) in self.layouts

    def supports_accum_dtype(self) -> bool:
        """Mixed-precision capable: planar kernels must opt in; canonical
        kernels always accumulate in float32 (the codec unpacks to c64)."""
        return self.supports_accum or self.form == CANONICAL

    def supports_compression(self) -> bool:
        """Two-row gauge capable: planar-view kernels must opt in; canonical
        kernels get it for free — the codec's unpack reconstructs row 2
        before they ever see the data (they just don't save the bytes)."""
        return self.supports_compressed or self.form == CANONICAL


_KERNELS: dict[str, KernelEntry] = {}


def register_kernel(
    name: str,
    *,
    layouts: Iterable[Layout] = (Layout.AOS, Layout.SOA, Layout.AOSOA),
    backends: Iterable[str] = ("xla",),
    form: str = CANONICAL,
    supports_fused: bool = False,
    supports_accum: bool = False,
    supports_compressed: bool = False,
) -> Callable[[Callable], Callable]:
    """Decorator registering ``fn`` as kernel ``name``; returns fn unchanged.

    Args:
        name: registry key; later registrations under the same name replace
            earlier ones (tests use this for stand-ins).
        layouts: physical layouts the kernel accepts (default: all three).
        backends: lowering backends (``"xla"`` and/or ``"pallas"``).
        form: ``CANONICAL`` (codec-wrapped complex) or ``PLANAR`` (direct
            planar view) — see :class:`KernelEntry` for the fn signatures.
        supports_fused: fn accepts ``k_iters`` (in-kernel chained multiply).
        supports_accum: fn accepts ``accum_dtype`` (planar kernels that own
            their upcast; canonical kernels get mixed precision for free).
        supports_compressed: fn accepts ``compressed`` (two-row gauge blocks
            with in-register row-2 reconstruction).

    Raises:
        ValueError: on an unknown ``form``.
    """
    if form not in (CANONICAL, PLANAR, STENCIL, STENCIL_AXPY):
        raise ValueError(f"unknown kernel form {form!r}")

    def deco(fn: Callable) -> Callable:
        _KERNELS[name] = KernelEntry(
            name=name,
            fn=fn,
            layouts=tuple(Layout(l) for l in layouts),
            backends=tuple(backends),
            form=form,
            supports_fused=supports_fused,
            supports_accum=supports_accum,
            supports_compressed=supports_compressed,
        )
        return fn

    return deco


def get_kernel(name: str) -> KernelEntry:
    """The registered entry for ``name``.

    Raises:
        KeyError: naming the known kernels, when ``name`` is unregistered.
    """
    try:
        return _KERNELS[name]
    except KeyError:
        raise KeyError(
            f"unknown SU3 kernel {name!r}; registered: {sorted(_KERNELS)}"
        ) from None


def kernel_names(
    *, backend: str | None = None, layout: Layout | None = None, form: str | None = None
) -> list[str]:
    """Sorted registered kernel names, optionally filtered.

    Args:
        backend: keep kernels lowered by this backend (``"xla"``/``"pallas"``).
        layout: keep kernels plannable with this physical layout.
        form: keep kernels of this form (``CANONICAL``/``PLANAR``).
    """
    out = []
    for name, entry in _KERNELS.items():
        if backend is not None and backend not in entry.backends:
            continue
        if layout is not None and not entry.supports_layout(layout):
            continue
        if form is not None and entry.form != form:
            continue
        out.append(name)
    return sorted(out)
