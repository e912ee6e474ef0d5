"""Model-vs-measured attribution: join spans against the roofline terms.

The table the paper's method demands: for every (tile, fused_k,
compression, depth) config that actually dispatched, line up the measured
span time against the three/four-term roofline prediction
(``autotune.predict_pipeline`` / ``autotune.predict_stencil``) and report
the delta — "this config is issue-bound and the model under-predicts the
halo by 18%" instead of a single GFLOPS number.

Span contract (what the serve/plan instrumentation emits):

  ``dispatch`` spans    attrs: kind ("multiply" | "stencil"), L, tile, k,
                        dtype, compression, host, live, flops, mode.
                        One span per host-step dispatch; ``flops`` are the
                        useful flops of the live requests in the batch.
  ``stencil.step``      attrs: L, tile, dtype, compression, hosts,
                        overlap, depth.  Child spans ``stencil.exchange`` /
                        ``stencil.interior`` / ``stencil.boundary`` (and
                        ``stencil.ring`` at depth 2) carry the phase times
                        that make ``overlap_efficiency`` a measured
                        quantity.

Rows accept live ``Span`` objects or JSONL record dicts interchangeably,
so ``scripts/trace_report.py`` can re-run the join offline from a trace
file.  The model side is priced against the peaks of the device kind the
trace recorded (never the reader's device, so reading a trace starts no JAX
backend); with no recorded kind, or on a machine without the stack, the
report degrades to measured-only rows (``predicted_gflops=None``).
"""
from __future__ import annotations

import statistics
from typing import Any, Iterable

_PHASE_NAMES = ("stencil.exchange", "stencil.interior", "stencil.boundary",
                "stencil.ring")


def _norm(rec: Any) -> dict[str, Any] | None:
    """Span | JSONL record -> {name, dur_s, attrs, span_id, parent_id}."""
    if hasattr(rec, "as_dict"):
        rec = rec.as_dict()
    if not isinstance(rec, dict) or rec.get("type", "span") != "span":
        return None
    return {
        "name": rec.get("name", ""),
        "dur_s": float(rec.get("dur_s", 0.0)),
        "attrs": rec.get("attrs", {}) or {},
        "span_id": rec.get("span_id"),
        "parent_id": rec.get("parent_id"),
    }


def _spans(records: Iterable[Any]) -> list[dict[str, Any]]:
    out = []
    for rec in records:
        norm = _norm(rec)
        if norm is not None:
            out.append(norm)
    return out


def _predictors(device_kind: str | None):
    """(autotune module, peaks of the recording device) or (None, None).

    The peaks are those of the ``device_kind`` the trace was recorded on,
    never the reader's; with no recorded kind the model side stays empty."""
    if device_kind is None:
        return None, None
    try:
        from repro.core import autotune, roofline
    except ImportError:  # pragma: no cover - jax-less trace readers
        return None, None
    return autotune, roofline.hardware_for_device_kind(device_kind)


def recorded_device_kind(records: Iterable[Any]) -> str | None:
    """The ``device_kind`` of a trace's ``{"type": "meta"}`` record, if any."""
    for rec in records:
        if isinstance(rec, dict) and rec.get("type") == "meta":
            return rec.get("device_kind")
    return None


# --------------------------------------------------------------------- joins
def _multiply_rows(spans: list[dict], autotune_mod, hw) -> list[dict]:
    groups: dict[tuple, list[dict]] = {}
    for s in spans:
        a = s["attrs"]
        if s["name"] != "dispatch" or a.get("kind") != "multiply":
            continue
        key = (int(a.get("L", 0)), int(a.get("tile", 0)), int(a.get("k", 1)),
               str(a.get("dtype", "float32")),
               str(a.get("compression", "none")))
        groups.setdefault(key, []).append(s)
    rows = []
    for (L, tile, k, dtype, compression), members in sorted(groups.items()):
        durs = [m["dur_s"] for m in members if m["dur_s"] > 0]
        flops = sum(float(m["attrs"].get("flops", 0.0)) for m in members)
        total_s = sum(m["dur_s"] for m in members)
        mults = sum(int(m["attrs"].get("live", 1)) for m in members) * k
        measured_per_mult_s = total_s / mults if mults else 0.0
        row = {
            "workload": "multiply",
            "L": L, "tile": tile, "fused_k": k,
            "dtype": dtype, "compression": compression, "depth": None,
            "n_spans": len(members),
            "measured_s": statistics.median(durs) if durs else 0.0,
            "measured_unit_s": measured_per_mult_s,
            "measured_gflops": (flops / total_s / 1e9) if total_s else 0.0,
        }
        if autotune_mod is not None and tile > 0 and L > 0:
            pred = autotune_mod.predict_pipeline(
                autotune_mod.PipelineCandidate(tile=tile, fused_k=k),
                L=L, dtype=dtype, hw=hw, compression=compression)
            row.update(_model_fields(pred, measured_per_mult_s))
        else:
            row.update(_model_fields(None, measured_per_mult_s))
        rows.append(row)
    return rows


def _stencil_dispatch_rows(spans: list[dict], autotune_mod, hw) -> list[dict]:
    groups: dict[tuple, list[dict]] = {}
    for s in spans:
        a = s["attrs"]
        if s["name"] != "dispatch" or a.get("kind") != "stencil":
            continue
        key = (int(a.get("L", 0)), int(a.get("tile", 0)),
               str(a.get("dtype", "float32")),
               str(a.get("compression", "none")))
        groups.setdefault(key, []).append(s)
    rows = []
    for (L, tile, dtype, compression), members in sorted(groups.items()):
        durs = [m["dur_s"] for m in members if m["dur_s"] > 0]
        flops = sum(float(m["attrs"].get("flops", 0.0)) for m in members)
        total_s = sum(m["dur_s"] for m in members)
        apps = sum(int(m["attrs"].get("live", 1))
                   * int(m["attrs"].get("k", 1)) for m in members)
        measured_per_app_s = total_s / apps if apps else 0.0
        row = {
            "workload": "stencil",
            "L": L, "tile": tile, "fused_k": None,
            "dtype": dtype, "compression": compression, "depth": 1,
            "n_spans": len(members),
            "measured_s": statistics.median(durs) if durs else 0.0,
            "measured_unit_s": measured_per_app_s,
            "measured_gflops": (flops / total_s / 1e9) if total_s else 0.0,
        }
        if autotune_mod is not None and tile > 0 and L > 0:
            pred = autotune_mod.predict_stencil(
                autotune_mod.StencilCandidate(tile=tile, overlap=False, depth=1),
                L=L, dtype=dtype, hosts=1, hw=hw, compression=compression)
            row.update(_model_fields(pred, measured_per_app_s))
        else:
            row.update(_model_fields(None, measured_per_app_s))
        rows.append(row)
    return rows


def _stencil_schedule_rows(spans: list[dict], autotune_mod, hw) -> list[dict]:
    """One row per traced (L, tile, overlap, depth, hosts, compression)
    schedule config, with per-phase measured seconds from child spans."""
    by_id = {s["span_id"]: s for s in spans if s["span_id"] is not None}
    steps: dict[tuple, list[dict]] = {}
    phases: dict[int, dict[str, float]] = {}
    for s in spans:
        if s["name"] == "stencil.step":
            a = s["attrs"]
            key = (int(a.get("L", 0)), int(a.get("tile", 0)),
                   bool(a.get("overlap", False)), int(a.get("depth", 1)),
                   int(a.get("hosts", 1)), str(a.get("dtype", "float32")),
                   str(a.get("compression", "none")))
            steps.setdefault(key, []).append(s)
        elif s["name"] in _PHASE_NAMES and s["parent_id"] in by_id:
            acc = phases.setdefault(s["parent_id"], {})
            short = s["name"].split(".", 1)[1]
            acc[short] = acc.get(short, 0.0) + s["dur_s"]
    rows = []
    for (L, tile, overlap, depth, hosts, dtype, compression), members in \
            sorted(steps.items()):
        durs = [m["dur_s"] for m in members if m["dur_s"] > 0]
        measured_s = statistics.median(durs) if durs else 0.0
        # per-application time: a depth-d step is d stencil applications
        measured_unit_s = measured_s / max(depth, 1)
        phase_s: dict[str, float] = {}
        n_phase_steps = 0
        for m in members:
            p = phases.get(m["span_id"])
            if p:
                n_phase_steps += 1
                for name, dur in p.items():
                    phase_s[name] = phase_s.get(name, 0.0) + dur
        if n_phase_steps:
            phase_s = {k: v / n_phase_steps for k, v in phase_s.items()}
        flops = sum(float(m["attrs"].get("flops", 0.0)) for m in members)
        total_s = sum(m["dur_s"] for m in members)
        row = {
            "workload": "stencil_schedule",
            "L": L, "tile": tile, "fused_k": None,
            "dtype": dtype, "compression": compression,
            "overlap": overlap, "depth": depth, "hosts": hosts,
            "n_spans": len(members),
            "measured_s": measured_s,
            "measured_unit_s": measured_unit_s,
            "measured_gflops": (flops / total_s / 1e9) if total_s else 0.0,
            "phase_s": {k: round(v, 9) for k, v in sorted(phase_s.items())},
            "measured_dominant_phase": (
                max(phase_s, key=phase_s.get) if phase_s else None),
        }
        if autotune_mod is not None and tile > 0 and L > 0:
            pred = autotune_mod.predict_stencil(
                autotune_mod.StencilCandidate(
                    tile=tile, overlap=overlap, depth=depth),
                L=L, dtype=dtype, hosts=hosts, hw=hw, compression=compression)
            row.update(_model_fields(pred, measured_unit_s))
        else:
            row.update(_model_fields(None, measured_unit_s))
        rows.append(row)
    return rows


def _model_fields(pred: dict | None, measured_unit_s: float) -> dict:
    """The model side of a row: predicted terms + the headline delta.

    ``delta_frac`` is (measured - predicted) / predicted on the per-unit
    time — positive means the model under-predicts (reality slower)."""
    if not pred:
        return {"predicted_s": None, "predicted_gflops": None,
                "model_dominant": None, "model_terms": None,
                "delta_frac": None}
    bound = float(pred["bound_s"])
    terms = {k: pred[k] for k in
             ("compute_s", "memory_s", "issue_s", "halo_s") if k in pred}
    return {
        "predicted_s": bound,
        "predicted_gflops": pred.get("predicted_gflops"),
        "model_dominant": pred.get("dominant"),
        "model_terms": terms,
        "delta_frac": ((measured_unit_s - bound) / bound) if bound else None,
    }


def attribution_report(records: Iterable[Any],
                       device_kind: str | None = None) -> list[dict]:
    """Measured-vs-modeled rows for every config that shows up in spans.

    Three workload families: ``multiply`` (serving dispatch, joined against
    predict_pipeline), ``stencil`` (serving dispatch, predict_stencil at
    hosts=1/serial), ``stencil_schedule`` (the overlap schedule's step +
    phase spans, predict_stencil at the traced (overlap, depth, hosts)).

    ``device_kind`` is the ``jax.Device.device_kind`` the spans were
    recorded on; when omitted it is read from the records' meta record.
    With neither, the rows carry the measured side only.
    """
    records = list(records)
    if device_kind is None:
        device_kind = recorded_device_kind(records)
    spans = _spans(records)
    autotune_mod, hw = _predictors(device_kind)
    rows = []
    rows.extend(_multiply_rows(spans, autotune_mod, hw))
    rows.extend(_stencil_dispatch_rows(spans, autotune_mod, hw))
    rows.extend(_stencil_schedule_rows(spans, autotune_mod, hw))
    return rows


# ------------------------------------------------------------ overlap measure
def overlap_efficiency_from_spans(records: Iterable[Any]) -> dict | None:
    """Phase accounting for the overlap schedule, straight from spans.

    Returns the mean per-step phase seconds plus the traced wall.  Because
    traced runs synchronize at phase boundaries (the only way to time a
    phase), the *traced* wall cannot witness hiding — the caller divides
    ``sum_phases_s`` by an UNTRACED wall to get the real efficiency
    (``overlap_efficiency = sum_phases / untraced_wall``; 1.0 means nothing
    hidden, >1 means the exchange overlapped the interior).
    """
    spans = _spans(records)
    steps = [s for s in spans if s["name"] == "stencil.step"
             and s["attrs"].get("overlap")]
    if not steps:
        return None
    ids = {s["span_id"] for s in steps}
    phase_s: dict[str, float] = {}
    for s in spans:
        if s["name"] in _PHASE_NAMES and s["parent_id"] in ids:
            short = s["name"].split(".", 1)[1]
            phase_s[short] = phase_s.get(short, 0.0) + s["dur_s"]
    n = len(steps)
    phase_s = {k: v / n for k, v in phase_s.items()}
    wall = sum(s["dur_s"] for s in steps) / n
    return {
        "n_steps": n,
        "phase_s": {k: round(v, 9) for k, v in sorted(phase_s.items())},
        "sum_phases_s": sum(phase_s.values()),
        "traced_wall_s": wall,
    }


def overlap_efficiency(sum_phases_s: float, untraced_wall_s: float) -> float:
    if untraced_wall_s <= 0:
        return 0.0
    return sum_phases_s / untraced_wall_s


# ---------------------------------------------------------------- rendering
_COLUMNS = ("workload", "config", "n", "measured", "modeled", "delta",
            "dominant", "gflops(meas/pred)")


def _fmt_s(v: float | None) -> str:
    if v is None:
        return "-"
    if v >= 1.0:
        return f"{v:.3f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.1f}us"


def _config_tag(row: dict) -> str:
    bits = [f"L{row['L']}", f"t{row['tile']}"]
    if row.get("fused_k"):
        bits.append(f"k{row['fused_k']}")
    if row.get("depth") and row["workload"] != "multiply":
        bits.append(f"d{row['depth']}")
    if row.get("hosts") and row.get("hosts", 1) > 1:
        bits.append(f"h{row['hosts']}")
    if row.get("overlap"):
        bits.append("ovl")
    if row.get("compression", "none") != "none":
        bits.append(row["compression"])
    if row.get("dtype", "float32") != "float32":
        bits.append(row["dtype"])
    return "/".join(bits)


def render_attribution(rows: list[dict]) -> str:
    """Fixed-width model-vs-measured table (the trace_report payload)."""
    if not rows:
        return "(no attributable dispatch/schedule spans in trace)"
    table = [_COLUMNS]
    for row in rows:
        delta = row.get("delta_frac")
        meas_g = row.get("measured_gflops")
        pred_g = row.get("predicted_gflops")
        dominant = row.get("model_dominant") or "-"
        if row.get("measured_dominant_phase"):
            dominant += f" (meas: {row['measured_dominant_phase']})"
        table.append((
            row["workload"],
            _config_tag(row),
            str(row["n_spans"]),
            _fmt_s(row.get("measured_unit_s")),
            _fmt_s(row.get("predicted_s")),
            f"{delta:+.0%}" if delta is not None else "-",
            dominant,
            (f"{meas_g:.2f}/{pred_g:.2f}"
             if meas_g is not None and pred_g is not None else "-"),
        ))
    widths = [max(len(r[i]) for r in table) for i in range(len(_COLUMNS))]
    lines = []
    for i, r in enumerate(table):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)
