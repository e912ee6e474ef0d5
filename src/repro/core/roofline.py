"""Three-term roofline analyzer — the paper's §5.3 insight as a library.

The paper's PIUMA finding is that two-term (compute, bandwidth) roofline is
insufficient: SU3_Bench on PIUMA is bounded by a *third* architectural rate,
the scalar pipeline's instruction issue rate (12 loads + 2 stores + 12 FMAs
per 24 flops -> 3.6 GF/s/core, below both the flops and bandwidth roofs).

At multi-pod TPU scale the third term is the interconnect: collective bytes
over ICI links. This module derives all three terms from a *compiled* (AOT)
XLA artifact — no hardware required, exactly like the paper derives the PIUMA
bound from instruction counts:

  compute_s    = HLO flops per device       / chip peak flops/s
  memory_s     = HLO bytes per device       / chip HBM bytes/s
  collective_s = sum over collective ops of ring-model time per device
  issue_s      = HLO instructions per device / pipeline issue slots/s
                 (the paper's §5.3 term itself, measurable when the spec
                 carries ``issue_rate`` and the cost model an instruction
                 count — what the roofline-pruned autotuner ranks with)

``cost_analysis()`` on an SPMD executable reports the **per-device** program
(verified empirically: an 8-way sharded matmul reports total/8 flops), so all
terms here are per-device seconds and directly comparable.

Collective bytes are *not* in cost_analysis: we parse the post-partitioning
HLO (``compiled.as_text()``) and apply standard ring-collective cost models
using each op's shape and replica-group size.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Mapping

# ---------------------------------------------------------------------------
# Hardware models.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float  # per chip, dense matmul path (bf16 MXU for TPU)
    peak_flops_vpu: float  # per chip, vector-unit path (fp32) — SU3's honest roof
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per ICI link
    ici_links: int  # usable links per chip
    hbm_bytes: float  # HBM capacity per chip
    vmem_bytes: float  # VMEM per core (Pallas tile budget)
    # The paper's §5.3 fourth rate: instruction-issue slots per second of the
    # scalar/VLIW pipeline that sequences the kernel (0 = not modeled).  One
    # "instruction" here is one issued op however wide its vector payload —
    # exactly why a wide-lane kernel can be issue-bound long before it is
    # flops- or bandwidth-bound.
    issue_rate: float = 0.0

    @property
    def ridge_flops_per_byte(self) -> float:
        return self.peak_flops / self.hbm_bw


# Published peaks of one chip (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect (200 GB/s, taken here as 4 links x 50 GB/s).  The f32 VPU peak
# and the issue rate are NOT published figures: they are models.
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    # 8 VPU lanes x 128 sublanes x 2 flops (FMA) x ~940 MHz ~= 1.9 TF/s fp32.
    peak_flops_vpu=1.9e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=16 * 1024**2,
    # one VPU/VMEM op issued per scalar-core cycle at ~940 MHz; each op covers
    # 8x128 lanes, so issue binds exactly when tiles are small or chains short
    issue_rate=0.94e9,
)

# The paper's two platforms, for the Xeon/PIUMA comparison benchmarks.
XEON_8280_SOCKET = HardwareSpec(
    name="clx8280_socket",  # paper §4: 28 cores, 2x AVX-512 FMA, 105 GB/s
    peak_flops=2420.1e9,
    peak_flops_vpu=2420.1e9,
    hbm_bw=105e9,
    ici_bw=10.4e9,  # one UPI link
    ici_links=3,
    hbm_bytes=96 * 1024**3,
    vmem_bytes=1 * 1024**2,  # L2 as the "tile" store
    issue_rate=3.0e11,  # 28 cores x 4-wide issue x ~2.7 GHz
)

PIUMA_CORE = HardwareSpec(
    name="piuma_core",  # paper §5.3: 8 GF/s FMA peak, BW-bound 4.32 GF/s
    peak_flops=8e9,
    peak_flops_vpu=8e9,
    hbm_bw=6.4e9,  # 4.32 GF/s at AI=0.675 -> 6.4 GB/s effective per core
    ici_bw=6.4e9,  # network bw >= local DRAM bw (paper §3.2)
    ici_links=1,
    hbm_bytes=1 * 1024**3,
    vmem_bytes=256 * 1024,  # SPAD
    # §5.3: 26 issued ops (12 loads + 2 stores + 12 FMAs) per 24 flops bound
    # the core at 3.6 GF/s -> 3.6e9 * 26/24 ~= 3.9e9 issue slots/s
    issue_rate=3.9e9,
)

HARDWARE = {h.name: h for h in (TPU_V5E, XEON_8280_SOCKET, PIUMA_CORE)}

# The peaks table, keyed by the ``device_kind`` jax reports for the chip.
PEAKS_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def hardware_for_device_kind(device_kind: str) -> HardwareSpec:
    """The peaks of the device whose ``jax.Device.device_kind`` is given:
    the kind recorded where the measurement was made, not the reader's.

    CPU rehearsals (kind ``"cpu"``) rank against the v5e model, the chip this
    stack targets.  Any other kind raises: a share of a peak nobody recorded
    is not a number.
    """
    if device_kind == "cpu":
        return TPU_V5E
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r}; add it "
            f"to roofline.PEAKS_BY_DEVICE_KIND with its source") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "token": 0, "opaque": 0,
}

# one shape, e.g. "bf16[16,4096,512]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\](?:\{[^}]*\})?")
_COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
# `%name = <shape or (tuple)> <kind>(` — post-optimization HLO one-liner form.
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+("
    + "|".join(_COLLECTIVE_KINDS)
    + r")(?:-start|-done)?\(",
)
_REPLICA_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")
_REPLICA_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")


def _shape_bytes(shape_text: str) -> int:
    """Bytes of one HLO shape string or tuple-of-shapes text."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int | None:
    m = _REPLICA_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]<=[total]
    m = _REPLICA_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return None


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int

    @property
    def link_bytes(self) -> float:
        """Ring-model bytes through one device's links.

        all-gather result is the full gathered tensor (per-device output);
        reduce-scatter result is the shard; all-reduce result == operand.
        """
        n = max(self.group_size, 1)
        s = self.result_bytes
        if n == 1:
            return 0.0
        if self.kind == "all-gather":
            return (n - 1) / n * s  # s = full tensor
        if self.kind == "reduce-scatter":
            return (n - 1) * s  # s = shard; (n-1)/n * full = (n-1)*shard
        if self.kind == "all-reduce":
            return 2 * (n - 1) / n * s
        if self.kind == "all-to-all":
            return (n - 1) / n * s
        if self.kind == "collective-permute":
            return float(s)
        return float(s)


def parse_collectives(hlo_text: str) -> list[CollectiveOp]:
    """Extract every collective op from post-partitioning HLO text."""
    ops: list[CollectiveOp] = []
    seen_started: set[str] = set()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.search(line)
        if not m:
            continue
        # Avoid double counting async pairs: `-done` carries no replica groups;
        # count `-start` (or the sync form) only.
        if re.search(r"-done\(", line):
            continue
        shape_text, kind = m.group(1), m.group(2)
        result_bytes = _shape_bytes(shape_text)
        group = _group_size(line)
        if group is None:
            group = 2  # collective-permute has no replica_groups; pairwise
        ops.append(CollectiveOp(kind=kind, result_bytes=result_bytes, group_size=group))
    return ops


def collective_bytes_by_kind(ops: list[CollectiveOp]) -> dict[str, float]:
    out: dict[str, float] = {}
    for op in ops:
        out[op.kind] = out.get(op.kind, 0.0) + op.link_bytes
    return out


# ---------------------------------------------------------------------------
# The three-term report.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RooflineReport:
    name: str
    hw: HardwareSpec
    n_chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_link_bytes: float
    collective_by_kind: dict[str, float]
    model_flops: float = 0.0  # 6*N*D useful flops (total, all devices)
    use_vpu_roof: bool = False  # SU3: vector-unit kernels can't see the MXU
    xla_flops_unscaled: float = 0.0  # raw cost_analysis (loop bodies once)
    xla_bytes_unscaled: float = 0.0
    # issued-instruction count per device (loop-aware, from the HLO mix) —
    # feeds the paper's §5.3 pipeline-throughput term; 0 = not measured
    instructions_per_device: float = 0.0
    instr_by_class: dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def peak(self) -> float:
        return self.hw.peak_flops_vpu if self.use_vpu_roof else self.hw.peak_flops

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peak

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.hw.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_link_bytes / self.hw.ici_bw

    @property
    def issue_s(self) -> float:
        """Pipeline-throughput term: issued instructions over the issue rate.

        The paper's PIUMA result in model form — SU3_Bench there is bounded
        neither by flops nor by bandwidth but by how fast the pipeline can
        *issue* its 12-load/2-store/12-FMA pattern.  Zero when either side is
        unmeasured/unmodeled, so two-term users are unaffected.
        """
        if not self.hw.issue_rate or not self.instructions_per_device:
            return 0.0
        return self.instructions_per_device / self.hw.issue_rate

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s, self.issue_s)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
            "issue": self.issue_s,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        total_hlo = self.flops_per_device * self.n_chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """How close the *useful* work runs to the binding roof: the score.

        useful_time_at_roof / bound_s where useful_time_at_roof is the time
        the dominant resource would need for MODEL_FLOPS alone.
        """
        if self.bound_s == 0:
            return 0.0
        useful_per_dev = self.model_flops / max(self.n_chips, 1)
        return (useful_per_dev / self.peak) / self.bound_s

    def as_dict(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "hw": self.hw.name,
            "n_chips": self.n_chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_link_bytes": self.collective_link_bytes,
            "collective_by_kind": self.collective_by_kind,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "issue_s": self.issue_s,
            "instructions_per_device": self.instructions_per_device,
            "instr_by_class": self.instr_by_class,
            "dominant": self.dominant,
            "bound_s": self.bound_s,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }

    def summary(self) -> str:
        return (
            f"{self.name}: compute {self.compute_s * 1e3:.3f} ms | "
            f"memory {self.memory_s * 1e3:.3f} ms | "
            f"collective {self.collective_s * 1e3:.3f} ms | "
            f"issue {self.issue_s * 1e3:.3f} ms "
            f"-> {self.dominant}-bound; useful/HLO flops "
            f"{self.useful_flops_ratio:.3f}, roofline frac {self.roofline_fraction:.3f}"
        )


def analyze_compiled(
    name: str,
    compiled: Any,
    *,
    n_chips: int,
    hw: HardwareSpec = TPU_V5E,
    model_flops: float = 0.0,
    use_vpu_roof: bool = False,
    hlo_text: str | None = None,
) -> RooflineReport:
    """Build a RooflineReport from a jax AOT ``compiled`` object.

    Uses the loop-aware HLO cost model (core.hlo_costs) — XLA's built-in
    cost_analysis counts while bodies once, which undercounts every scanned
    layer stack. The raw cost_analysis numbers are kept as a cross-check.
    """
    from repro.core import hlo_costs

    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_costs.analyze_hlo(text)
    raw: Mapping[str, float] = {}
    try:
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):  # older jax: [per-device dict]
            ca = ca[0] if ca else {}
        raw = ca
    except Exception:
        pass
    return RooflineReport(
        name=name,
        hw=hw,
        n_chips=n_chips,
        flops_per_device=cost.flops,
        bytes_per_device=cost.bytes,
        collective_link_bytes=cost.collective_link_bytes,
        collective_by_kind=dict(cost.collective_by_kind),
        model_flops=model_flops,
        use_vpu_roof=use_vpu_roof,
        xla_flops_unscaled=float(raw.get("flops", 0.0)),
        xla_bytes_unscaled=float(raw.get("bytes accessed", 0.0)),
        instructions_per_device=cost.instructions,
        instr_by_class=dict(cost.instr_by_class),
    )


def analytic_su3_report(
    *,
    n_sites: int,
    word_bytes: int,
    bytes_per_site_rw: int,
    n_chips: int = 1,
    hw: HardwareSpec = TPU_V5E,
) -> RooflineReport:
    """Paper-style analytic roofline for the SU3 kernel (no compile needed)."""
    flops = 864.0 * n_sites
    byts = float(bytes_per_site_rw) * n_sites
    return RooflineReport(
        name=f"su3_analytic_L4={n_sites}",
        hw=hw,
        n_chips=n_chips,
        flops_per_device=flops / n_chips,
        bytes_per_device=byts / n_chips,
        collective_link_bytes=0.0,
        collective_by_kind={},
        model_flops=flops,
        use_vpu_roof=True,
    )
