"""SU3 autotune: the paper's §4/§5.4 methodology as a driver, with a cache.

Hillclimbs the SU3 kernel the way the paper does — enumerate candidates,
napkin-math the expected effect, measure, keep the winner:

  * layout sweep charges the traffic model (AOS streams 320 B/site vs SoA
    288 B — the paper's streaming-store/padding point) and cross-checks it
    at the HLO level by lowering the *physical* ExecutionPlan step, so the
    packed layout actually shows up in the counted bytes;
  * the **pipeline sweep** enumerates the joint (tile, fused_k) grid,
    *ranks* it with the three-term roofline model — memory (traffic model,
    amortized over the fused chain), compute (VPU roof), and the paper's
    §5.3 **issue-rate term**, estimated from the lowered kernel's
    instruction mix — and only MEASURES the top ``prune`` fraction.  The
    exhaustive sweep's measurement bill drops by >= 2x while the model keeps
    the true winner inside the measured set (asserted by tests);
  * ``best_config`` selects the candidate with the best *measured* GFLOPS
    among verified, VMEM-fitting candidates and persists the decision —
    tile, fused chain depth, and the ``pipeline`` provenance block (schema
    version, candidates ranked vs measured, predicted rank of the winner) —
    in a JSON cache keyed by (schema, backend, device_kind, layout, dtype,
    L, n_devices).  A second call loads the tuned plan with zero
    measurements, so engines, serving, and benchmarks all start from the
    tuned tuple for free.

Cache schema: v3 (the ``compression`` axis on multiply configs and the
``depth`` axis on stencil configs).  Keys carry the version, so v1/v2
entries simply miss and re-measure — they are never read with missing
fields.

Cache location: ``$REPRO_SU3_CACHE_DIR`` or ``~/.cache/repro_su3``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import hlo_costs, roofline
from repro.core.su3 import layouts, registry, variants
from repro.core.su3.engine import EngineConfig, SU3Engine
from repro.core.su3.layouts import Layout
from repro.core.su3.plan import make_raw_step
from repro.kernels import su3_matmul, su3_stencil

CACHE_ENV = "REPRO_SU3_CACHE_DIR"
CACHE_FILE = "su3_autotune.json"
SCHEMA_VERSION = 3  # v3: compression axis in the key + depth axis on stencils
DEFAULT_PRUNE = 0.5  # measure the top half of the model-ranked candidates
DEFAULT_TILES = (128, 256, 512, 1024, 2048, 4096)
DEFAULT_KS = (1, 2, 4, 8)
DEFAULT_DEPTHS = (1, 2)  # halo exchange depths the stencil sweep considers
# per-dispatch fixed cost in issue slots (kernel launch + grid sequencing);
# amortized over the fused chain, which is what makes deep K win at small L
DISPATCH_ISSUE_SLOTS = 5_000.0
# fixed per-exchange latency (collective setup + neighbor sync), the term a
# depth-2 communication-avoiding schedule amortizes over two applications
HALO_EXCHANGE_LATENCY_S = 2e-5


@dataclasses.dataclass
class TuneResult:
    config: dict[str, Any]
    measured_gflops: float
    hlo_bytes_per_site: float
    model_bytes_per_site: float
    vmem_bytes: int
    v5e_bound_gf: float


# ---------------------------------------------------------------------------
# HLO-level accounting
# ---------------------------------------------------------------------------


def hlo_bytes_for_variant(
    variant: str,
    layout: layouts.Layout,
    n_sites: int = 4096,
    tile: int = 512,
    dtype: str = "float32",
    accum_dtype: str = "",
    compression: str = "none",
) -> float:
    """Lower the *physical* plan step through XLA; count HLO bytes per site.

    The operands are packed per the requested layout before lowering (via the
    layout codec), so AOS genuinely streams its 80-word sites and SOA its
    72-word sites — previously the canonical complex operands were lowered
    for every non-Pallas variant and the ``layout`` argument was ignored,
    making the AOS and SOA rows identical.

    ``dtype``/``accum_dtype`` lower the mixed-precision storage plans: a
    bf16-storage / f32-accumulate plan streams 2-byte operands and results,
    so its measured bytes/site land well under the f32 plan's even though
    every FMA runs at f32 (converts are charged at the narrow side — the
    paper-correct streaming cost).

    ``compression="two_row"`` lowers the 12-real gauge plan: the packed
    operand physically carries 48 words/site and the kernel reconstructs the
    third row in-register, so the compressed bytes show up in the counted
    HLO traffic rather than being asserted from the model.
    """
    codec = layouts.make_codec(
        layout, tile=tile, dtype=dtype, accum_dtype=accum_dtype,
        compression=layouts.GaugeCompression(compression),
    )
    entry = registry.get_kernel(variant)
    interpret = True if entry.form == registry.PLANAR else None
    step = make_raw_step(codec, entry, tile=tile, interpret=interpret)
    pad = (-n_sites) % tile
    a = jnp.zeros((n_sites + pad, 4, 3, 3), jnp.complex64)
    a_phys = codec.pack(a)
    b_p = codec.pack_b(jnp.zeros((4, 3, 3), jnp.complex64))
    compiled = jax.jit(step).lower(a_phys, b_p).compile()
    cost = hlo_costs.analyze_hlo(compiled.as_text())
    return cost.bytes / (n_sites + pad)  # bytes per site actually lowered


# ---------------------------------------------------------------------------
# Sweeps
# ---------------------------------------------------------------------------


def tile_sweep(
    tiles: tuple[int, ...] = (128, 256, 512, 1024, 2048, 4096),
    L: int = 8,
    dtype: str = "float32",
    accum_dtype: str = "",
) -> list[dict]:
    """VMEM working set + measured engine time per Pallas tile.

    The working-set bound honors the sweep's dtypes: bf16 storage halves the
    resident tile bytes, while a wider accumulate re-inflates them (the
    upcast tiles are what actually sit in VMEM).

    Exhaustive marginal sweep (every tile at k=1), kept for the CLI and
    diagnostics; production tuning goes through the roofline-pruned joint
    :func:`pipeline_sweep`.
    """
    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    rows = []
    for tile in tiles:
        vmem = su3_matmul.vmem_bytes(tile, word_b, accum_b)
        fits = vmem <= roofline.TPU_V5E.vmem_bytes
        cfg = EngineConfig(L=L, dtype=dtype, variant="pallas", layout=layouts.Layout.SOA,
                           tile=tile, accum_dtype=accum_dtype, iterations=2, warmups=1)
        r = SU3Engine(cfg).run()
        rows.append({
            "tile": tile, "vmem_kib": vmem // 1024, "fits_vmem": fits,
            "measured_gflops": round(r.gflops, 3), "verified": r.verified,
        })
    return rows


def k_sweep(
    ks: tuple[int, ...] = (1, 2, 4, 8),
    L: int = 8,
    dtype: str = "float32",
    tile: int = 512,
    accum_dtype: str = "",
) -> list[dict]:
    """Measured per-multiply GFLOPS of the fused chain at each depth K.

    The fused step amortizes one dispatch (and on TPU one HBM roundtrip) over
    K multiplies, but past some K the chain stops helping — longer in-kernel
    chains grow the straight-line body (or fall to the fori_loop) without
    removing any more overhead.  The knee depends on (backend, L), so it is
    measured, not assumed, and ``best_config`` persists the winner next to
    the tile.
    """
    rows = []
    for k in ks:
        cfg = EngineConfig(L=L, dtype=dtype, variant="pallas", layout=layouts.Layout.SOA,
                           tile=tile, accum_dtype=accum_dtype, iterations=2, warmups=1)
        r = SU3Engine(cfg).run_fused(k=k, reps=2)
        rows.append({
            "k": k, "measured_gflops": round(r.gflops, 3), "verified": r.verified,
        })
    return rows


def layout_sweep(n_sites: int = 4096) -> list[dict]:
    """The paper's AoS->SoA traffic claim, measured at the HLO level.

    The bf16-storage / f32-accumulate row is the MILC-on-KNL reduced-
    precision-storage scheme; the ``two_row`` rows stack the 12-real gauge
    compression on top (48 words/site streamed, third row reconstructed
    in-register), both measured at the HLO level rather than assumed.
    """
    rows = []
    for variant, layout, dtype, accum, comp in (
            ("versionX", layouts.Layout.AOS, "float32", "", "none"),
            ("versionX", layouts.Layout.SOA, "float32", "", "none"),
            ("version_gemm", layouts.Layout.SOA, "float32", "", "none"),
            ("pallas", layouts.Layout.SOA, "float32", "", "none"),
            ("pallas", layouts.Layout.SOA, "bfloat16", "float32", "none"),
            ("pallas", layouts.Layout.SOA, "float32", "", "two_row"),
            ("pallas", layouts.Layout.SOA, "bfloat16", "float32", "two_row")):
        tm = layouts.TrafficModel.for_dtype(
            layout, n_sites, dtype, compression=layouts.GaugeCompression(comp)
        )
        hlo_b = hlo_bytes_for_variant(variant, layout, n_sites,
                                      dtype=dtype, accum_dtype=accum,
                                      compression=comp)
        bound = roofline.TPU_V5E.hbm_bw * tm.arithmetic_intensity / 1e9
        rows.append({
            "variant": variant, "layout": layout.value, "dtype": dtype,
            "accum_dtype": accum or dtype, "compression": comp,
            "model_bytes_per_site": tm.bytes_per_site_rw,
            "hlo_bytes_per_site": round(hlo_b, 1),
            "ai": round(tm.arithmetic_intensity, 3),
            "v5e_bound_gf": round(bound, 1),
        })
    return rows


# ---------------------------------------------------------------------------
# Roofline-pruned pipeline sweep: rank the (tile, fused_k) grid with the
# three-term model, measure only the top fraction.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineCandidate:
    """One point of the joint (Pallas tile, fused chain depth) grid."""

    tile: int
    fused_k: int


def enumerate_candidates(
    tiles: tuple[int, ...] = DEFAULT_TILES,
    ks: tuple[int, ...] = DEFAULT_KS,
    dtype: str = "float32",
    accum_dtype: str = "",
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
) -> list[PipelineCandidate]:
    """The VMEM-fitting (tile, fused_k) grid — the exhaustive candidate set
    the pruner ranks.  Tiles whose resident working set (at the wider of
    storage/accumulate width) exceeds ``hw``'s tile store never become
    candidates."""
    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    return [
        PipelineCandidate(tile, k)
        for tile in tiles
        if su3_matmul.vmem_bytes(tile, word_b, accum_b) <= hw.vmem_bytes
        for k in ks
    ]


_INSTR_MODEL_CACHE: dict[tuple[str, str, int, str], tuple[float, float]] = {}


def kernel_instruction_model(
    dtype: str = "float32", accum_dtype: str = "", tile: int = 256,
    compression: str = "none",
) -> tuple[float, float]:
    """(base, per_multiply) issued-instruction counts of ONE kernel grid step.

    Estimated from the *lowered* kernel's instruction mix, the way the paper
    derives the PIUMA bound from its 12-load/2-store/12-FMA pattern: lower
    the fused planar kernel at chain depths 1 and 2 over a single-tile grid
    and difference the loop-aware HLO instruction counts —

        instructions_per_step(k) ~= base + per_multiply * k

    where ``base`` is the fixed staging cost (tile load/store, bookkeeping)
    and ``per_multiply`` the chained-FMA body.  Instruction counts are
    vector-ISSUE counts: one op however wide its lane payload, which is
    exactly why a larger tile lowers the issue bill per site.
    """
    key = (dtype, accum_dtype, tile, compression)
    if key not in _INSTR_MODEL_CACHE:
        codec = layouts.make_codec(
            Layout.SOA, tile=tile, dtype=dtype, accum_dtype=accum_dtype,
            compression=layouts.GaugeCompression(compression),
        )
        entry = registry.get_kernel("pallas")

        def instrs(k: int) -> float:
            step = make_raw_step(codec, entry, tile=tile, k_iters=k, interpret=True)
            a_p = jnp.zeros((2, codec.planar_rows, tile), codec.word_dtype)
            b_p = jnp.zeros((2, layouts.PLANAR_ROWS), codec.word_dtype)
            compiled = jax.jit(step).lower(a_p, b_p).compile()
            return hlo_costs.analyze_hlo(compiled.as_text()).instructions

        i1, i2 = instrs(1), instrs(2)
        per_mult = max(i2 - i1, 1.0)
        base = max(i1 - per_mult, 0.0)
        _INSTR_MODEL_CACHE[key] = (base, per_mult)
    return _INSTR_MODEL_CACHE[key]


def predict_pipeline(
    cand: PipelineCandidate,
    L: int,
    dtype: str = "float32",
    accum_dtype: str = "",
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
    compression: str = "none",
) -> dict[str, Any]:
    """Three-term per-multiply roofline prediction for one candidate.

    memory_s amortizes the one HBM read + write over the fused chain (the
    chain runs on the VMEM-resident tile), compute_s is the VPU roof, and
    issue_s charges the instruction mix of ``grid_steps`` kernel steps plus
    the per-dispatch launch cost, both amortized over the chain — the three
    rates whose max is the predicted bound.
    """
    n_sites = L**4
    padded = ((n_sites + cand.tile - 1) // cand.tile) * cand.tile
    k = cand.fused_k
    tm = layouts.TrafficModel.for_dtype(
        Layout.SOA, padded, dtype,
        compression=layouts.GaugeCompression(compression),
    )
    # every term charges the PADDED work (what the kernel executes); the
    # predicted throughput credits only the USEFUL flops (what the engine
    # reports), so an oversized tile at small L ranks as badly as it measures
    compute_s = float(tm.flops_per_site) * padded / hw.peak_flops_vpu
    memory_s = tm.total_bytes / k / hw.hbm_bw
    issue_s = 0.0
    if hw.issue_rate:
        base, per_mult = kernel_instruction_model(
            dtype, accum_dtype, compression=compression
        )
        grid_steps = padded // cand.tile
        instrs = grid_steps * (base / k + per_mult) + DISPATCH_ISSUE_SLOTS / k
        issue_s = instrs / hw.issue_rate
    bound_s = max(compute_s, memory_s, issue_s)
    terms = {"compute": compute_s, "memory": memory_s, "issue": issue_s}
    useful_flops = float(tm.flops_per_site) * n_sites  # per multiply
    return {
        "tile": cand.tile,
        "fused_k": k,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "issue_s": issue_s,
        "bound_s": bound_s,
        "dominant": max(terms, key=terms.get),
        "predicted_gflops": round(useful_flops / bound_s / 1e9, 3),
    }


def measure_candidate(
    cand: PipelineCandidate, L: int = 8, dtype: str = "float32",
    accum_dtype: str = "", compression: str = "none",
) -> dict[str, Any]:
    """Measured per-multiply GFLOPS of one (tile, fused_k) candidate — the
    fused chain run exactly as it deploys."""
    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    vmem = su3_matmul.vmem_bytes(cand.tile, word_b, accum_b)
    cfg = EngineConfig(
        L=L, dtype=dtype, variant="pallas", layout=Layout.SOA,
        tile=cand.tile, accum_dtype=accum_dtype, iterations=2, warmups=1,
        compression=compression,
    )
    r = SU3Engine(cfg).run_fused(k=cand.fused_k, reps=2)
    return {
        "tile": cand.tile,
        "fused_k": cand.fused_k,
        "vmem_kib": vmem // 1024,
        "measured_gflops": round(r.gflops, 3),
        "verified": r.verified,
    }


def pipeline_sweep(
    L: int = 8,
    dtype: str = "float32",
    accum_dtype: str = "",
    *,
    compression: str = "none",
    prune: float = DEFAULT_PRUNE,
    tiles: tuple[int, ...] = DEFAULT_TILES,
    ks: tuple[int, ...] = DEFAULT_KS,
    measure_fn: Callable[[PipelineCandidate], dict[str, Any]] | None = None,
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
) -> dict[str, Any]:
    """Rank the candidate grid with the roofline model; measure the top slice.

    Args:
        prune: fraction of the model-ranked candidate set to measure
            (``>= 1`` = exhaustive; the default measures half).  At least
            one candidate is always measured.
        measure_fn: measurement override (tests inject deterministic
            measurements; production uses :func:`measure_candidate`).

    Returns:
        ``{"rows", "candidates_total", "candidates_measured", "prune"}`` —
        each row carries the model prediction (compute/memory/issue seconds,
        predicted GFLOPS, ``predicted_rank``) joined with the measurement.
    """
    cands = enumerate_candidates(tiles, ks, dtype, accum_dtype, hw)
    if not cands:
        raise RuntimeError("no VMEM-fitting pipeline candidate")
    preds = [
        predict_pipeline(c, L, dtype, accum_dtype, hw, compression=compression)
        for c in cands
    ]
    order = sorted(range(len(cands)), key=lambda i: -preds[i]["predicted_gflops"])
    n_meas = len(cands) if prune >= 1 else max(1, math.ceil(prune * len(cands)))
    if measure_fn is None:
        measure_fn = lambda c: measure_candidate(  # noqa: E731
            c, L=L, dtype=dtype, accum_dtype=accum_dtype, compression=compression
        )
    rows = []
    for rank, i in enumerate(order[:n_meas]):
        row = dict(preds[i])
        row.update(measure_fn(cands[i]))
        row["predicted_rank"] = rank
        rows.append(row)
    return {
        "rows": rows,
        "candidates_total": len(cands),
        "candidates_measured": n_meas,
        "prune": prune,
    }


# ---------------------------------------------------------------------------
# Roofline-pruned stencil sweep: rank (tile, overlap) stencil variants with a
# model whose bandwidth term includes the halo exchange, measure the top
# fraction.  The stencil is the first workload where the PR 3 halo model is a
# *schedule* input rather than a price list: overlap on/off changes whether
# halo seconds add to the core roofline bound or hide under it.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StencilCandidate:
    """One point of the stencil variant grid: Pallas site tile x whether the
    interior/boundary overlap schedule is used x halo exchange depth (a
    depth-d exchange ships d ghost rings and runs d stencil applications per
    exchange, recomputing the intermediate ring locally)."""

    tile: int
    overlap: bool
    depth: int = 1


def enumerate_stencil_candidates(
    tiles: tuple[int, ...] = DEFAULT_TILES,
    overlaps: tuple[bool, ...] = (False, True),
    dtype: str = "float32",
    accum_dtype: str = "",
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
    depths: tuple[int, ...] = DEFAULT_DEPTHS,
) -> list[StencilCandidate]:
    """The VMEM-fitting (tile, overlap, depth) grid the stencil pruner ranks.
    The stencil grid step resides U + 8 neighbor + out tiles, so its VMEM
    bound is tighter than the multiply's at the same tile.  Depth > 1 exists
    only on the overlap schedule (the communication-avoiding step-2 path is
    built from the overlap machinery), so (overlap=False, depth=2) is never
    a candidate."""
    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    return [
        StencilCandidate(tile, ov, d)
        for tile in tiles
        if su3_stencil.stencil_vmem_bytes(tile, word_b, accum_b) <= hw.vmem_bytes
        for ov in overlaps
        for d in depths
        if ov or d == 1
    ]


_STENCIL_INSTR_CACHE: dict[tuple[str, str, str], float] = {}
_STENCIL_INSTR_TILE = 256  # fixed lowering tile: issue counts are vector-
# ISSUE counts (one op however wide the lane payload), so per-step cost is
# tile-independent — same convention as kernel_instruction_model


def stencil_instruction_model(
    dtype: str = "float32", accum_dtype: str = "", compression: str = "none"
) -> float:
    """Issued-instruction count of ONE stencil kernel grid step, from the
    lowered kernel's loop-aware instruction mix (same method as
    :func:`kernel_instruction_model`; the stencil has no chain-depth knob, so
    a single lowering at a fixed tile suffices)."""
    key = (dtype, accum_dtype, compression)
    if key not in _STENCIL_INSTR_CACHE:
        tile = _STENCIL_INSTR_TILE
        entry = registry.get_kernel("pallas_stencil")
        wdt = jnp.dtype(dtype)
        kw: dict[str, Any] = {"tile": tile, "interpret": True}
        if accum_dtype:
            kw["accum_dtype"] = accum_dtype
        rows = layouts.PLANAR_ROWS
        if compression == layouts.GaugeCompression.TWO_ROW.value:
            kw["compressed"] = True
            rows = layouts.PLANAR_COMP_ROWS
        u = jnp.zeros((2, rows, tile), wdt)
        vn = jnp.zeros((8, 2, 3, tile), wdt)
        compiled = (
            jax.jit(lambda u, vn: entry.fn(u, vn, **kw)).lower(u, vn).compile()
        )
        _STENCIL_INSTR_CACHE[key] = float(
            hlo_costs.analyze_hlo(compiled.as_text()).instructions
        )
    return _STENCIL_INSTR_CACHE[key]


def _stencil_halo_spec(L: int, hosts: int, word_bytes: int, depth: int = 1):
    """Vector-field HaloSpec for ``hosts`` slabs (0 halo on one host)."""
    from repro.distributed import sharding as dist_sharding

    return dist_sharding.HaloSpec(
        L=L, n_shards=max(hosts, 1), word_bytes=word_bytes,
        words_per_site=dist_sharding.VECTOR_WORDS_PER_SITE, depth=depth,
    )


def predict_stencil(
    cand: StencilCandidate,
    L: int,
    dtype: str = "float32",
    accum_dtype: str = "",
    hosts: int = 1,
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
    compression: str = "none",
) -> dict[str, Any]:
    """Roofline prediction for one stencil variant, halo bytes included.

    Every quantity is PER STENCIL APPLICATION, so depth-1 and depth-2 rows
    compare directly.  The core terms are the usual three (memory streams
    U + 8 neighbor fields + out — 102 words/site when the gauge field is
    two-row compressed, 150 full; VPU compute at 576 flops/site; instruction
    issue per grid step plus per-dispatch launch cost).  The fourth term is
    the halo: one depth-d exchange ships d ghost rings
    (``HaloSpec.halo_bytes_per_exchange`` at 6 words/site) plus pays one
    fixed ``HALO_EXCHANGE_LATENCY_S``, and buys d applications — so the
    per-application halo time divides by depth.  The byte half of that term
    is roughly depth-invariant (d rings / d applications); the LATENCY half
    is what the communication-avoiding schedule actually halves.

    All shards run concurrently, so the wall-clock bound is a PER-SHARD
    quantity: the core terms (computed for the full lattice on one chip)
    scale by ``1/hosts`` before composing with the per-shard halo time.
    Schedule semantics:

    * ``overlap=False`` — compute serializes behind the exchange:
      ``bound = core/hosts + halo``;
    * ``overlap=True``  — the exchange hides under the interior pass and the
      boundary sites are recomputed after it lands; a depth-d schedule
      additionally recomputes the intermediate ghost ring locally, one
      boundary-sized slab per application:
      ``bound = max(core/hosts, halo) + depth * boundary_fraction * core/hosts``
      (``boundary_fraction`` is already shard-relative:
      ``boundary_sites / sites_per_shard``).

    ``bandwidth_bytes`` in the returned row is the full per-application
    bandwidth-term payload — streamed bytes plus the exchanged halo bytes
    amortized over the depth — which is what the benchmark rows persist (the
    acceptance bar: halo bytes are IN the bandwidth term, not a footnote).
    """
    n_sites = L**4
    padded = ((n_sites + cand.tile - 1) // cand.tile) * cand.tile
    wb = layouts.WORD_BYTES[dtype]
    compressed = compression == layouts.GaugeCompression.TWO_ROW.value
    words_site = (su3_stencil.STENCIL_COMP_WORDS_PER_SITE if compressed
                  else su3_stencil.STENCIL_WORDS_PER_SITE)
    stream_bytes = padded * words_site * wb
    compute_s = float(su3_stencil.STENCIL_FLOPS_PER_SITE) * padded / hw.peak_flops_vpu
    memory_s = stream_bytes / hw.hbm_bw
    issue_s = 0.0
    n_dispatches = 3 if (cand.overlap and hosts > 1) else 1
    if hw.issue_rate:
        per_step = stencil_instruction_model(dtype, accum_dtype, compression)
        instrs = (padded // cand.tile) * per_step + DISPATCH_ISSUE_SLOTS * n_dispatches
        issue_s = instrs / hw.issue_rate
    core_s = max(compute_s, memory_s, issue_s)
    # every shard computes 1/hosts of the lattice, all shards concurrently —
    # the wall bound composes the PER-SHARD core with the per-shard halo
    core_shard_s = core_s / max(hosts, 1)
    halo = _stencil_halo_spec(L, hosts, wb, depth=cand.depth)
    halo_s = (
        HALO_EXCHANGE_LATENCY_S + halo.halo_bytes_per_exchange / hw.ici_bw
    ) / cand.depth
    boundary_frac = (  # shard-relative: boundary_sites / sites_per_shard
        halo.boundary_sites / halo.sites_per_shard if hosts > 1 else 0.0
    )
    if hosts == 1:
        bound_s = core_s
    elif cand.overlap:
        bound_s = max(core_shard_s, halo_s) + cand.depth * boundary_frac * core_shard_s
    else:
        bound_s = core_shard_s + halo_s
    useful = float(su3_stencil.STENCIL_FLOPS_PER_SITE) * n_sites
    terms = {"compute": compute_s, "memory": memory_s, "issue": issue_s,
             "halo": halo_s if hosts > 1 else 0.0}
    return {
        "tile": cand.tile,
        "overlap": cand.overlap,
        "depth": cand.depth,
        "compression": compression,
        "hosts": hosts,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "issue_s": issue_s,
        "core_shard_s": core_shard_s,
        "halo_s": halo_s if hosts > 1 else 0.0,
        "bound_s": bound_s,
        "dominant": max(terms, key=terms.get),
        "halo_bytes_per_exchange": halo.halo_bytes_per_exchange,
        "bandwidth_bytes": stream_bytes + halo.halo_bytes_per_exchange // cand.depth,
        "boundary_fraction": round(boundary_frac, 4),
        "predicted_gflops": round(useful / bound_s / 1e9, 3),
    }


def measure_stencil_candidate(
    cand: StencilCandidate, L: int = 8, dtype: str = "float32",
    accum_dtype: str = "", compression: str = "none",
) -> dict[str, Any]:
    """Measured per-application GFLOPS of one stencil variant on the local
    mesh (useful flops = 576/site; a depth-d step runs d applications per
    dispatch, so its wall time divides by d).  Overlap on a single local
    host degenerates to the interior-only schedule — the model's hosts>1
    halo term is what separates the variants; measurement keeps selection
    honest about kernel cost.  Depth-2 candidates are additionally verified
    BITWISE against two reference (depth-1) applications — the
    communication-avoiding schedule must change scheduling only, never
    values."""
    from repro.core.su3.plan import build_plan
    from repro.core.su3.engine import EngineConfig

    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    cfg = EngineConfig(
        L=L, dtype=dtype, variant="pallas", layout=Layout.SOA,
        tile=cand.tile, accum_dtype=accum_dtype, iterations=2, warmups=1,
        compression=compression,
    )
    plan = build_plan(cfg)
    step = plan.stencil_step(overlap=cand.overlap, depth=cand.depth)
    u, v = plan.init_stencil_data()
    out = step(u, v)  # warm/compile; also the output 'verified' judges
    out.block_until_ready()
    import time as _time

    best = float("inf")
    for _ in range(2):
        t0 = _time.perf_counter()
        step(u, v).block_until_ready()
        best = min(best, _time.perf_counter() - t0)
    verified = bool(plan.verify_stencil(out)) if cand.depth == 1 else bool(
        jnp.array_equal(
            out,
            plan.stencil_step(overlap=False, depth=1)(
                u, plan.stencil_step(overlap=False, depth=1)(u, v)
            ),
        )
    )
    gf = cand.depth * su3_stencil.STENCIL_FLOPS_PER_SITE * (L**4) / best / 1e9
    return {
        "tile": cand.tile,
        "overlap": cand.overlap,
        "depth": cand.depth,
        "vmem_kib": su3_stencil.stencil_vmem_bytes(cand.tile, word_b, accum_b) // 1024,
        "measured_gflops": round(gf, 3),
        "verified": verified,
    }


def stencil_sweep(
    L: int = 8,
    dtype: str = "float32",
    accum_dtype: str = "",
    *,
    hosts: int = 1,
    compression: str = "none",
    prune: float = DEFAULT_PRUNE,
    tiles: tuple[int, ...] = DEFAULT_TILES,
    overlaps: tuple[bool, ...] = (False, True),
    depths: tuple[int, ...] = DEFAULT_DEPTHS,
    measure_fn: Callable[[StencilCandidate], dict[str, Any]] | None = None,
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
) -> dict[str, Any]:
    """Rank the stencil (tile, overlap, depth) grid with the halo-charging
    roofline model; measure only the top ``prune`` fraction — the stencil
    analogue of :func:`pipeline_sweep`, with the same return structure and
    the same selection contract (tests gate it at within-5%-of-exhaustive)."""
    cands = enumerate_stencil_candidates(
        tiles, overlaps, dtype, accum_dtype, hw, depths
    )
    if not cands:
        raise RuntimeError("no VMEM-fitting stencil candidate")
    preds = [
        predict_stencil(c, L, dtype, accum_dtype, hosts, hw, compression=compression)
        for c in cands
    ]
    order = sorted(range(len(cands)), key=lambda i: -preds[i]["predicted_gflops"])
    n_meas = len(cands) if prune >= 1 else max(1, math.ceil(prune * len(cands)))
    if measure_fn is None:
        measure_fn = lambda c: measure_stencil_candidate(  # noqa: E731
            c, L=L, dtype=dtype, accum_dtype=accum_dtype, compression=compression
        )
    rows = []
    for rank, i in enumerate(order[:n_meas]):
        row = dict(preds[i])
        row.update(measure_fn(cands[i]))
        row["predicted_rank"] = rank
        rows.append(row)
    return {
        "rows": rows,
        "candidates_total": len(cands),
        "candidates_measured": n_meas,
        "prune": prune,
    }


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


def cache_dir() -> str:
    return os.environ.get(
        CACHE_ENV, os.path.join(os.path.expanduser("~"), ".cache", "repro_su3")
    )


def cache_key(
    *,
    backend: str,
    device_kind: str,
    layout: str,
    dtype: str,
    L: int,
    n_devices: int,
    compression: str = "none",
    schema: int = SCHEMA_VERSION,
) -> str:
    """Versioned cache key.  The ``v{schema}`` prefix is the invalidation
    mechanism: entries written before the pipeline sweep (v1) or before the
    compression/depth axes (v2) simply never match a v3 lookup and re-measure
    cleanly instead of being read with missing fields.  ``compression`` is a
    key segment, not a suffix on dtype, so an 18-real and a two-row decision
    for the same (dtype, L) never alias."""
    return (
        f"v{schema}|{backend}|{device_kind}|{layout}|{dtype}"
        f"|{compression}|L{L}|d{n_devices}"
    )


def _cache_path(directory: str | None) -> str:
    return os.path.join(directory or cache_dir(), CACHE_FILE)


def load_cache(directory: str | None = None) -> dict[str, Any]:
    path = _cache_path(directory)
    try:
        with open(path) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def store_cache_entry(
    key: str, entry: dict[str, Any], directory: str | None = None
) -> None:
    """Read-modify-write the cache file via an atomic rename."""
    path = _cache_path(directory)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    cache = load_cache(directory)
    cache[key] = entry
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def _device_identity() -> tuple[str, str, int]:
    devs = jax.devices()
    return jax.default_backend(), devs[0].device_kind, len(devs)


# ---------------------------------------------------------------------------
# The tuned production config
# ---------------------------------------------------------------------------


# keys a cached config must carry to be served without re-measuring; entries
# written by older builds (no fused_k; no pipeline block; no compression) or
# truncated by a crashed writer fall through to a fresh sweep instead of
# KeyError-ing every caller.  The versioned cache_key already isolates
# v1/v2 entries — this guard additionally catches a v3-keyed entry written
# incompletely.
_REQUIRED_CONFIG_KEYS = frozenset(
    {"layout", "variant", "tile", "fused_k", "compression", "pipeline"}
)


def _valid_cache_hit(hit: Any) -> dict[str, Any] | None:
    """The cached config dict iff the entry is structurally sound."""
    if not isinstance(hit, dict):
        return None
    config = hit.get("config")
    if not isinstance(config, dict) or not _REQUIRED_CONFIG_KEYS <= config.keys():
        return None
    return config


def best_config(
    L: int = 8,
    dtype: str = "float32",
    *,
    accum_dtype: str = "",
    compression: str = "none",
    cache: bool = True,
    cache_directory: str | None = None,
    refresh: bool = False,
    prune: float = DEFAULT_PRUNE,
    measure_fn: Callable[[PipelineCandidate], dict[str, Any]] | None = None,
) -> dict[str, Any]:
    """The tuned production config: SoA + the (tile, fused_k) pipeline point
    with the best MEASURED GFLOPS among the roofline-ranked top candidates.

    The joint grid is ranked by the three-term model (memory amortized over
    the chain, VPU compute, instruction-issue rate) and only the top
    ``prune`` fraction is measured — selection stays by measured throughput
    among verified, VMEM-fitting candidates, the model just decides what is
    worth timing.  The decision is persisted with its ``pipeline``
    provenance (schema version, candidate counts, the winner's predicted
    rank); later calls (any process) with the same versioned
    (backend, device_kind, layout, dtype, L, n_devices) key do zero
    measurements.  Pre-pipeline (v1) and pre-compression (v2) entries never
    match the v3 key, and corrupt or partial v3 entries (truncated writes,
    missing ``pipeline`` block) are treated as misses and re-measured, never
    crashed on.

    ``accum_dtype`` tunes mixed-precision plans as deployed: the sweep runs
    the f32-accumulate kernel (different VMEM resident set, instruction mix,
    and fused-K knee than the pure storage dtype), and the cache key carries
    the accumulate width so bf16-pure and bf16+f32-accum decisions never
    alias.  ``compression="two_row"`` tunes the 12-real gauge plan the same
    way, under its own key segment.
    """
    backend, device_kind, n_devices = _device_identity()
    dtype_key = f"{dtype}+acc-{accum_dtype}" if accum_dtype else dtype
    key = cache_key(
        backend=backend, device_kind=device_kind, layout="soa",
        dtype=dtype_key, L=L, n_devices=n_devices, compression=compression,
    )
    if cache and not refresh:
        config = _valid_cache_hit(load_cache(cache_directory).get(key))
        if config is not None:
            return dict(config, cached=True)

    sweep = pipeline_sweep(
        L=L, dtype=dtype, accum_dtype=accum_dtype, compression=compression,
        prune=prune, measure_fn=measure_fn,
    )
    rows = [r for r in sweep["rows"] if r["verified"]]
    if not rows:
        raise RuntimeError("no verified pipeline candidate in the measured set")
    winner = max(rows, key=lambda r: r["measured_gflops"])
    config = {
        "layout": "soa", "variant": "pallas",
        "tile": winner["tile"], "fused_k": winner["fused_k"],
        "compression": compression,
        "pipeline": {
            "schema": SCHEMA_VERSION,
            "prune": sweep["prune"],
            "candidates_total": sweep["candidates_total"],
            "candidates_measured": sweep["candidates_measured"],
            "predicted_gflops": winner.get("predicted_gflops", 0.0),
            "predicted_rank": winner.get("predicted_rank", 0),
        },
    }
    if cache:
        store_cache_entry(
            key,
            {"config": config, "measured_gflops": winner["measured_gflops"], "key": key},
            cache_directory,
        )
    return dict(config, cached=False)


# stencil cache entries carry (tile, overlap, depth, stencil provenance)
# instead of the multiply tuple's (tile, fused_k, pipeline); they live under
# their own layout key ("soa-stencil") so the two shapes never alias.
_REQUIRED_STENCIL_KEYS = frozenset(
    {"layout", "variant", "tile", "overlap", "depth", "stencil"}
)


def _valid_stencil_hit(hit: Any) -> dict[str, Any] | None:
    if not isinstance(hit, dict):
        return None
    config = hit.get("config")
    if not isinstance(config, dict) or not _REQUIRED_STENCIL_KEYS <= config.keys():
        return None
    return config


def best_stencil_config(
    L: int = 8,
    dtype: str = "float32",
    *,
    accum_dtype: str = "",
    compression: str = "none",
    hosts: int = 1,
    cache: bool = True,
    cache_directory: str | None = None,
    refresh: bool = False,
    prune: float = DEFAULT_PRUNE,
    measure_fn: Callable[[StencilCandidate], dict[str, Any]] | None = None,
) -> dict[str, Any]:
    """The tuned stencil variant: the (tile, overlap, depth) point with the
    best MEASURED GFLOPS among the halo-aware-roofline-ranked top candidates.

    Same contract as :func:`best_config` — ranked by model, selected by
    measurement among verified candidates, persisted with provenance under a
    versioned key (layout ``soa-stencil``, so multiply and stencil decisions
    never alias) — with ``hosts`` entering both the ranking (the halo term)
    and the cache key (a 1-host and a 4-host schedule tune differently).
    """
    backend, device_kind, n_devices = _device_identity()
    dtype_key = f"{dtype}+acc-{accum_dtype}" if accum_dtype else dtype
    key = cache_key(
        backend=backend, device_kind=device_kind, layout=f"soa-stencil-h{hosts}",
        dtype=dtype_key, L=L, n_devices=n_devices, compression=compression,
    )
    if cache and not refresh:
        config = _valid_stencil_hit(load_cache(cache_directory).get(key))
        if config is not None:
            return dict(config, cached=True)

    sweep = stencil_sweep(
        L=L, dtype=dtype, accum_dtype=accum_dtype, hosts=hosts,
        compression=compression, prune=prune, measure_fn=measure_fn,
    )
    rows = [r for r in sweep["rows"] if r["verified"]]
    if not rows:
        raise RuntimeError("no verified stencil candidate in the measured set")
    # The TILE is decided by measurement; the SCHEDULE axes (overlap, depth)
    # by the halo model.  On the local (single-host) measurement mesh the
    # schedules of a tile compile to near-identical per-application work —
    # overlap degenerates to the interior-only pass — so measured GFLOPS
    # cannot separate them and timer jitter would pick the persisted flags
    # at random.  The model is the only witness of the inter-host halo the
    # flags exist for.
    best_tile = max(rows, key=lambda r: r["measured_gflops"])["tile"]
    same_tile = [r for r in rows if r["tile"] == best_tile]
    # deterministic tie-break: when the model cannot separate the schedules
    # (hosts=1 predicts identical bounds), prefer the simpler serial one and
    # the shallower exchange — never let measured jitter of identical
    # compilations decide
    winner = max(
        same_tile,
        key=lambda r: (r["predicted_gflops"], not r["overlap"], -r.get("depth", 1)),
    )
    config = {
        "layout": "soa", "variant": "pallas_stencil",
        "tile": winner["tile"], "overlap": winner["overlap"],
        "depth": winner.get("depth", 1),
        "stencil": {
            "schema": SCHEMA_VERSION,
            "prune": sweep["prune"],
            "hosts": hosts,
            "compression": compression,
            "candidates_total": sweep["candidates_total"],
            "candidates_measured": sweep["candidates_measured"],
            "predicted_gflops": winner.get("predicted_gflops", 0.0),
            "predicted_rank": winner.get("predicted_rank", 0),
            "halo_bytes_per_exchange": winner.get("halo_bytes_per_exchange", 0),
        },
    }
    if cache:
        store_cache_entry(
            key,
            {"config": config, "measured_gflops": winner["measured_gflops"], "key": key},
            cache_directory,
        )
    return dict(config, cached=False)


# ---------------------------------------------------------------------------
# CG iteration tuning: the solve's hot loop is ONE fused stencil+axpy pass
# plus a shared scalar epilogue per iteration, so its decision axes are the
# Pallas tile and whether to run the fused kernel at all — the fused pass
# saves materializing the search direction p' as a standalone HBM round trip
# but pays a SECOND gathered neighbor field, so which side wins is a
# measured question, not a modeled one.  Decisions persist under their own
# cache key (layout "soa-cg-h{hosts}") so multiply/stencil/CG tuples for the
# same (dtype, L) never alias.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CGCandidate:
    """One point of the CG iteration grid: Pallas site tile x whether the
    iteration body runs the fused stencil+axpy kernel or the composed
    (axpy, stencil, shift) oracle path."""

    tile: int
    fused: bool = True


def enumerate_cg_candidates(
    tiles: tuple[int, ...] = DEFAULT_TILES,
    fused: tuple[bool, ...] = (True, False),
    dtype: str = "float32",
    accum_dtype: str = "",
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
) -> list[CGCandidate]:
    """The VMEM-fitting (tile, fused) grid the CG pruner ranks.  The fused
    grid step resides the stencil tile set PLUS the second gathered field,
    so its VMEM bound is tighter than the stencil's at the same tile; the
    composed path is bounded by the plain stencil step."""
    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    out = []
    for tile in tiles:
        for f in fused:
            bound = (su3_stencil.cg_vmem_bytes(tile, word_b, accum_b) if f
                     else su3_stencil.stencil_vmem_bytes(tile, word_b, accum_b))
            if bound <= hw.vmem_bytes:
                out.append(CGCandidate(tile, f))
    return out


# streamed storage words per site of ONE CG iteration (coarse, for ranking
# only — selection is by measurement).  Fused: the kernel streams U, BOTH
# gathered fields, the two center vectors, and two outputs; composed swaps
# the second gather for a standalone axpy round trip.  Both pay the shared
# epilogue (shift + x/r update + two reductions).
_CG_EPILOGUE_WORDS = 18 + 30 + 12 + 6  # shift, update, <p,Ap>, <r,r>


def _cg_words_per_site(fused: bool, compressed: bool) -> int:
    u_words = 2 * (layouts.PLANAR_COMP_ROWS if compressed else layouts.PLANAR_ROWS)
    if fused:
        body = u_words + 2 * 48 + 2 * 6 + 2 * 6  # u, r/p gathers, r/p, p'/s out
    else:
        body = 18 + (u_words + 48 + 6)  # axpy pass, then stencil pass
    return body + _CG_EPILOGUE_WORDS


def predict_cg(
    cand: CGCandidate,
    L: int,
    dtype: str = "float32",
    accum_dtype: str = "",
    hosts: int = 1,
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
    compression: str = "none",
) -> dict[str, Any]:
    """Roofline prediction for one CG iteration variant.

    Same three core terms as the stencil model (the stencil chain dominates
    the iteration's compute), with the memory stream swapped for the CG word
    count and the per-iteration halo charged like a depth-1 stencil exchange
    — the fused path's overlap schedule ships the ±t ghosts of BOTH fields
    but still pays one exchange per iteration.  Deliberately coarse: the
    model ranks tiles, measurement separates fused from composed.
    """
    n_sites = L**4
    padded = ((n_sites + cand.tile - 1) // cand.tile) * cand.tile
    wb = layouts.WORD_BYTES[dtype]
    compressed = compression == layouts.GaugeCompression.TWO_ROW.value
    stream_bytes = padded * _cg_words_per_site(cand.fused, compressed) * wb
    flops_site = float(su3_stencil.CG_ITER_FLOPS_PER_SITE)
    compute_s = flops_site * padded / hw.peak_flops_vpu
    memory_s = stream_bytes / hw.hbm_bw
    issue_s = 0.0
    n_dispatches = (4 if hosts > 1 else 2) if cand.fused else (5 if hosts > 1 else 3)
    if hw.issue_rate:
        per_step = stencil_instruction_model(dtype, accum_dtype, compression)
        instrs = (padded // cand.tile) * per_step + DISPATCH_ISSUE_SLOTS * n_dispatches
        issue_s = instrs / hw.issue_rate
    core_s = max(compute_s, memory_s, issue_s)
    core_shard_s = core_s / max(hosts, 1)
    halo = _stencil_halo_spec(L, hosts, wb, depth=1)
    halo_s = HALO_EXCHANGE_LATENCY_S + 2 * halo.halo_bytes_per_exchange / hw.ici_bw
    bound_s = core_s if hosts == 1 else max(core_shard_s, halo_s)
    useful = flops_site * n_sites
    return {
        "tile": cand.tile,
        "fused": cand.fused,
        "compression": compression,
        "hosts": hosts,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "issue_s": issue_s,
        "bound_s": bound_s,
        "bandwidth_bytes": stream_bytes,
        "predicted_gflops": round(useful / bound_s / 1e9, 3),
    }


def _cg_measure_problem(L: int, seed: int = 7) -> tuple[Any, Any]:
    """Deterministic convergent CG problem: a constant-per-direction SU(3)
    gauge field (each U_mu constant along mu, so the site-local-adjoint
    stencil is exactly Hermitian) and a unit-scale right-hand side."""
    import numpy as np

    rng = np.random.default_rng(seed)
    a = rng.normal(size=(4, 3, 3)) + 1j * rng.normal(size=(4, 3, 3))
    q, r = np.linalg.qr(a)
    d = np.diagonal(r, axis1=-2, axis2=-1)
    q = q * (d / np.abs(d))[..., None, :]
    q = q / np.linalg.det(q)[..., None, None] ** (1.0 / 3.0)
    n = L**4
    u = np.broadcast_to(q, (n, 4, 3, 3)).astype(np.complex64)
    b = (rng.normal(size=(n, 3)) + 1j * rng.normal(size=(n, 3))).astype(
        np.complex64
    )
    return layouts.on_host(lambda: (jnp.asarray(u), jnp.asarray(b)))


def measure_cg_candidate(
    cand: CGCandidate, L: int = 8, dtype: str = "float32",
    accum_dtype: str = "", compression: str = "none", iters: int = 4,
) -> dict[str, Any]:
    """Measured per-iteration GFLOPS of one CG variant on the local mesh
    (useful flops = ``CG_ITER_FLOPS_PER_SITE``/site/iteration).  Fused
    candidates are verified against the composed oracle — BITWISE at f32
    storage (the bit-identity contract), within ``plan.verify_tolerance``
    otherwise; the composed candidate is the oracle and verifies by its
    residual actually shrinking."""
    from repro.core.su3.plan import build_plan
    from repro.core.su3.engine import EngineConfig

    word_b = layouts.WORD_BYTES[dtype]
    accum_b = layouts.WORD_BYTES[accum_dtype] if accum_dtype else None
    cfg = EngineConfig(
        L=L, dtype=dtype, variant="pallas", layout=Layout.SOA,
        tile=cand.tile, accum_dtype=accum_dtype, iterations=2, warmups=1,
        compression=compression,
    )
    plan = build_plan(cfg)
    u, b = _cg_measure_problem(L)
    u_phys = plan.pack_gauge(u)
    b_p = plan.pack_rhs(b)

    def run(fused: bool, n: int):
        state = plan.cg_state_init(b_p)
        for _ in range(n):
            state = plan.cg_iterate(u_phys, state, fused=fused)
        jax.block_until_ready(state["rs"])
        return state

    state = run(cand.fused, iters)  # warm/compile; also the verify subject
    import time as _time

    best = float("inf")
    for _ in range(2):
        t0 = _time.perf_counter()
        run(cand.fused, iters)
        best = min(best, _time.perf_counter() - t0)

    b_rs = float(jax.device_get(plan.cg_state_init(b_p)["rs"]))
    rs = float(jax.device_get(state["rs"]))
    if cand.fused:
        oracle = run(False, iters)
        if dtype == "float32":
            verified = bool(jnp.array_equal(state["x"], oracle["x"])) and bool(
                jnp.array_equal(state["r"], oracle["r"])
            )
        else:
            tol = plan.verify_tolerance()
            o_rs = float(jax.device_get(oracle["rs"]))
            verified = abs((rs / b_rs) ** 0.5 - (o_rs / b_rs) ** 0.5) <= tol
    else:
        verified = rs < b_rs  # the oracle must at least be converging
    vmem = (su3_stencil.cg_vmem_bytes(cand.tile, word_b, accum_b) if cand.fused
            else su3_stencil.stencil_vmem_bytes(cand.tile, word_b, accum_b))
    gf = (
        su3_stencil.CG_ITER_FLOPS_PER_SITE * (L**4) * iters / best / 1e9
    )
    return {
        "tile": cand.tile,
        "fused": cand.fused,
        "vmem_kib": vmem // 1024,
        "measured_gflops": round(gf, 3),
        "verified": verified,
    }


def cg_sweep(
    L: int = 8,
    dtype: str = "float32",
    accum_dtype: str = "",
    *,
    hosts: int = 1,
    compression: str = "none",
    prune: float = DEFAULT_PRUNE,
    tiles: tuple[int, ...] = DEFAULT_TILES,
    fused: tuple[bool, ...] = (True, False),
    measure_fn: Callable[[CGCandidate], dict[str, Any]] | None = None,
    hw: roofline.HardwareSpec = roofline.TPU_V5E,
) -> dict[str, Any]:
    """Rank the CG (tile, fused) grid with the coarse iteration roofline;
    measure only the top ``prune`` fraction — same return structure and
    selection contract as :func:`pipeline_sweep` / :func:`stencil_sweep`."""
    cands = enumerate_cg_candidates(tiles, fused, dtype, accum_dtype, hw)
    if not cands:
        raise RuntimeError("no VMEM-fitting CG candidate")
    preds = [
        predict_cg(c, L, dtype, accum_dtype, hosts, hw, compression=compression)
        for c in cands
    ]
    order = sorted(range(len(cands)), key=lambda i: -preds[i]["predicted_gflops"])
    n_meas = len(cands) if prune >= 1 else max(1, math.ceil(prune * len(cands)))
    if measure_fn is None:
        measure_fn = lambda c: measure_cg_candidate(  # noqa: E731
            c, L=L, dtype=dtype, accum_dtype=accum_dtype, compression=compression
        )
    rows = []
    for rank, i in enumerate(order[:n_meas]):
        row = dict(preds[i])
        row.update(measure_fn(cands[i]))
        row["predicted_rank"] = rank
        rows.append(row)
    return {
        "rows": rows,
        "candidates_total": len(cands),
        "candidates_measured": n_meas,
        "prune": prune,
    }


# CG cache entries carry (tile, fused, cg provenance) under their own layout
# key ("soa-cg-h{hosts}") so they never alias multiply or stencil decisions.
_REQUIRED_CG_KEYS = frozenset({"layout", "variant", "tile", "fused", "cg"})


def _valid_cg_hit(hit: Any) -> dict[str, Any] | None:
    if not isinstance(hit, dict):
        return None
    config = hit.get("config")
    if not isinstance(config, dict) or not _REQUIRED_CG_KEYS <= config.keys():
        return None
    return config


def best_cg_config(
    L: int = 8,
    dtype: str = "float32",
    *,
    accum_dtype: str = "",
    compression: str = "none",
    hosts: int = 1,
    cache: bool = True,
    cache_directory: str | None = None,
    refresh: bool = False,
    prune: float = DEFAULT_PRUNE,
    measure_fn: Callable[[CGCandidate], dict[str, Any]] | None = None,
) -> dict[str, Any]:
    """The tuned CG iteration: the (tile, fused) point with the best
    MEASURED per-iteration GFLOPS among the verified candidates.

    Same contract as :func:`best_config` / :func:`best_stencil_config` —
    ranked by model, selected by measurement among verified candidates,
    persisted with provenance under a versioned key (layout
    ``soa-cg-h{hosts}``, so the CG decision never aliases the multiply or
    stencil tuple for the same dtype/L).  ``fused`` is a genuinely measured
    axis: the fused kernel trades a standalone p' round trip for a second
    gathered neighbor field, and which side of that trade wins depends on
    the gather cost of the backend actually serving the solve.
    """
    backend, device_kind, n_devices = _device_identity()
    dtype_key = f"{dtype}+acc-{accum_dtype}" if accum_dtype else dtype
    key = cache_key(
        backend=backend, device_kind=device_kind, layout=f"soa-cg-h{hosts}",
        dtype=dtype_key, L=L, n_devices=n_devices, compression=compression,
    )
    if cache and not refresh:
        config = _valid_cg_hit(load_cache(cache_directory).get(key))
        if config is not None:
            return dict(config, cached=True)

    sweep = cg_sweep(
        L=L, dtype=dtype, accum_dtype=accum_dtype, hosts=hosts,
        compression=compression, prune=prune, measure_fn=measure_fn,
    )
    rows = [r for r in sweep["rows"] if r["verified"]]
    if not rows:
        raise RuntimeError("no verified CG candidate in the measured set")
    winner = max(rows, key=lambda r: r["measured_gflops"])
    config = {
        "layout": "soa", "variant": "pallas_cg",
        "tile": winner["tile"], "fused": winner["fused"],
        "cg": {
            "schema": SCHEMA_VERSION,
            "prune": sweep["prune"],
            "hosts": hosts,
            "compression": compression,
            "candidates_total": sweep["candidates_total"],
            "candidates_measured": sweep["candidates_measured"],
            "predicted_gflops": winner.get("predicted_gflops", 0.0),
            "predicted_rank": winner.get("predicted_rank", 0),
        },
    }
    if cache:
        store_cache_entry(
            key,
            {"config": config, "measured_gflops": winner["measured_gflops"], "key": key},
            cache_directory,
        )
    return dict(config, cached=False)


def tuned_engine_config(
    L: int = 8, dtype: str = "float32", *, cache_directory: str | None = None, **overrides
) -> EngineConfig:
    """EngineConfig built from the (cached) tuned tuple, override-able.

    An ``accum_dtype`` or ``compression`` override also steers the tuning
    itself (such plans are measured as deployed, under their own cache key).
    """
    tuned = best_config(
        L=L, dtype=dtype, accum_dtype=overrides.get("accum_dtype", ""),
        compression=overrides.get("compression", "none"),
        cache_directory=cache_directory,
    )
    base = {
        "L": L, "dtype": dtype, "layout": layouts.Layout(tuned["layout"]),
        "variant": tuned["variant"], "tile": tuned["tile"],
        "compression": tuned.get("compression", "none"),
    }
    base.update(overrides)
    return EngineConfig(**base)


def tuned_fused_k(
    L: int = 8, dtype: str = "float32", *, accum_dtype: str = "",
    compression: str = "none", cache_directory: str | None = None
) -> int:
    """The measured-best fused chain depth for (backend, L) — from the cache.

    Serving and benchmarks call this instead of hardcoding K; the first call
    per device identity pays the sweep, every later process reads the cache.
    """
    return int(best_config(L=L, dtype=dtype, accum_dtype=accum_dtype,
                           compression=compression,
                           cache_directory=cache_directory)["fused_k"])


if __name__ == "__main__":
    print("== tile sweep (VMEM blocking, exhaustive marginal) ==")
    for r in tile_sweep():
        print("  ", r)
    print("== k sweep (fused chain depth, exhaustive marginal) ==")
    for r in k_sweep():
        print("  ", r)
    print("== layout sweep (traffic) ==")
    for r in layout_sweep():
        print("  ", r)
    print("== pipeline sweep (roofline-pruned joint (tile, fused_k)) ==")
    for r in pipeline_sweep()["rows"]:
        print("  ", r)
    print("best:", best_config())
