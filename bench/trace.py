"""Reduce a ``jax.profiler`` trace to device busy time, per-op time and idle gaps.

The profiler writes an XSpace (``<dir>/plugins/profile/<run>/*.xplane.pb``).
Each chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per HLO op that ran, named by the op's HLO text
(``%su3_mult_planar.1 = f32[...] custom-call(...)``).  Host planes hold the
benchmark's ``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``)
and the runtime's own host events on the same clock.

Ops are classified from the HLO text alone, not from the program's function
names: a ``custom-call`` is a Pallas/Mosaic kernel, anything else (fusions,
copies, gathers, reductions) is XLA glue around it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."
KERNEL = "kernel"
GLUE = "glue"
MIN_GAP_NS = 1_000  # idle stretches shorter than 1 us are issue latency


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


def op_name(hlo_text: str) -> str:
    """``%fusion.5 = f32[...] fusion(...)`` -> ``fusion.5``."""
    m = re.match(r"%?([^\s=]+)", hlo_text)
    return m.group(1) if m else hlo_text


def op_kind(hlo_text: str) -> str:
    """``kernel`` for a custom call (a Pallas/Mosaic kernel), else ``glue``."""
    return KERNEL if "custom-call(" in hlo_text else GLUE


def union_ns(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Reduced:
    """One traced window.

    ``busy_s`` is the union of op intervals on each chip, averaged over the
    chips that ran any; ``window_s`` spans the benchmark's annotations;
    ``op_s`` sums each op's durations (all chips); ``gaps`` sums the idle
    stretches of the first chip by what the host was doing in them.
    """

    window_s: float
    busy_s: float
    op_s: dict[str, float]
    op_kind: dict[str, str]
    gaps: dict[str, float]

    def kind_s(self, kind: str) -> float:
        return sum(s for name, s in self.op_s.items() if self.op_kind[name] == kind)

    @property
    def op_total_s(self) -> float:
        return sum(self.op_s.values())

    def glue_share(self) -> float | None:
        """Percent of op time outside kernels; None where nothing ran."""
        total = self.op_total_s
        if total <= 0:
            return None
        return 100.0 * (total - self.kind_s(KERNEL)) / total

    def idle_share(self) -> float | None:
        """Percent of the window in which the device ran no op."""
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, n: int = 10) -> dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[f"{k} ({self.op_kind[k]})", v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _label_gaps(gaps, annotations, host):
    """Sum each idle gap under the innermost benchmark annotation covering its
    midpoint, joined with the innermost runtime host event that covers at
    least half of it (an XLA execution, a transfer, ...)."""
    out: dict[str, float] = collections.defaultdict(float)
    a_s = np.array([e.start_ns for e in annotations]) if annotations else np.zeros(0)
    a_e = np.array([e.end_ns for e in annotations]) if annotations else np.zeros(0)
    h_s = np.array([e.start_ns for e in host]) if host else np.zeros(0)
    h_e = np.array([e.end_ns for e in host]) if host else np.zeros(0)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label = "outside annotations"
        cover = np.nonzero((a_s <= mid) & (a_e >= mid))[0]
        if cover.size:
            i = cover[np.argmin(a_e[cover] - a_s[cover])]
            label = annotations[i].name
        cover = np.nonzero((h_s <= mid) & (h_e >= mid)
                           & (np.minimum(h_e, e) - np.maximum(h_s, s) >= 0.5 * (e - s)))[0]
        if cover.size:
            i = cover[np.argmin(h_e[cover] - h_s[cover])]
            label = f"{label} / {host[i].name}"
        out[label] += (e - s) * 1e-9
    return dict(out)


def reduce_events(device: list[list[Event]], annotations: list[Event],
                  host: list[Event]) -> Reduced:
    """The reduction itself, on plain events: ``device`` holds each chip's
    op events, ``annotations`` the benchmark's spans, ``host`` the other
    host events."""
    device = [ops for ops in device if ops]
    if annotations:
        lo = min(e.start_ns for e in annotations)
        hi = max(e.end_ns for e in annotations)
    elif device:
        lo = min(e.start_ns for ops in device for e in ops)
        hi = max(e.end_ns for ops in device for e in ops)
    else:
        lo = hi = 0.0
    op_s: dict[str, float] = collections.defaultdict(float)
    kinds: dict[str, str] = {}
    busy = []
    for ops in device:
        covered = union_ns(_clip([(e.start_ns, e.end_ns) for e in ops], lo, hi))
        busy.append(sum(e - s for s, e in covered) * 1e-9)
        for e in ops:
            name = op_name(e.name)
            op_s[name] += (e.end_ns - e.start_ns) * 1e-9
            kinds[name] = op_kind(e.name)
    gaps: dict[str, float] = {}
    if device:
        covered = union_ns(_clip([(e.start_ns, e.end_ns) for e in device[0]], lo, hi))
        edges = [lo] + [x for iv in covered for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] >= MIN_GAP_NS]
        gaps = _label_gaps(idle, annotations, host)
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) if busy else 0.0,
                   op_s=dict(op_s), op_kind=kinds, gaps=gaps)


def events_of(profile) -> tuple[list[list[Event]], list[Event], list[Event]]:
    """Split a ``jax.profiler.ProfileData`` into device ops, benchmark
    annotations and other host events."""
    device, annotations, host = [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name, e.start_ns, e.end_ns) for e in line.events)
            device.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.end_ns)
                    (annotations if e.name.startswith(ANNOTATION_PREFIX)
                     else host).append(ev)
    return device, annotations, host


def reduce_profile(profile) -> Reduced:
    return reduce_events(*events_of(profile))


def reduce_dir(log_dir: str) -> Reduced:
    """Reduce the one trace ``jax.profiler.trace(log_dir)`` wrote."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, found {paths}")
    return reduce_profile(ProfileData.from_file(paths[0]))
