#!/usr/bin/env python
"""Render a repro.obs trace: span tree + model-vs-measured attribution.

Reads either export format the tracer writes — flat JSONL (one record per
line) or Chrome trace-event JSON (``{"traceEvents": [...]}``) — and prints:

  1. the span tree, aggregated by name-path (count, total, mean), so the
     request lifecycle (admit -> seat -> dispatch -> request) and the
     stencil phase nesting (stencil.step > exchange/interior/boundary)
     read at a glance;
  2. counters, if any were recorded;
  3. overlap-phase accounting when the trace holds overlapped
     ``stencil.step`` spans (per-phase seconds; the real efficiency needs
     an untraced wall — see ``benchmarks.stencil``);
  4. the attribution table: every traced (tile, fused_k, compression,
     depth) config joined against the pipeline/stencil roofline
     (``repro.obs.attribution``).  On a jax-less machine the model side
     degrades to ``-`` and the measured columns still render.

    PYTHONPATH=src python scripts/trace_report.py  # artifacts/serve_trace.jsonl
    python scripts/trace_report.py artifacts/serve_trace.chrome.json  # same report

Exit code 0 iff the report rendered (used by scripts/smoke.sh to assert a
traced serving run produced a readable trace).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

try:
    from repro.obs import attribution_report, render_attribution
    from repro.obs.attribution import overlap_efficiency_from_spans
    from repro.obs.tracer import load_jsonl
except ImportError:  # direct invocation without PYTHONPATH=src
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    from repro.obs import attribution_report, render_attribution
    from repro.obs.attribution import overlap_efficiency_from_spans
    from repro.obs.tracer import load_jsonl


def _split_meta(records: list[dict]) -> tuple[list[dict], dict]:
    """Flat JSONL keeps its metadata in a leading ``{"type": "meta"}`` record."""
    meta: dict = {}
    rest = []
    for rec in records:
        if rec.get("type") == "meta":
            meta = {k: v for k, v in rec.items() if k != "type"}
        else:
            rest.append(rec)
    return rest, meta


def load_records(path: str) -> tuple[list[dict], dict]:
    """(records, metadata) from a JSONL or Chrome trace-event file."""
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except json.JSONDecodeError:  # multiple lines -> flat JSONL
        return _split_meta(load_jsonl(path))
    if not isinstance(payload, dict) or "traceEvents" not in payload:
        # a single-record JSONL file parses as one object
        return _split_meta([payload] if isinstance(payload, dict) else [])
    records = []
    for ev in payload.get("traceEvents", []):
        args = dict(ev.get("args") or {})
        records.append({
            "type": "span",
            "name": ev.get("name", ""),
            "ts_s": ev.get("ts", 0.0) / 1e6,
            "dur_s": ev.get("dur", 0.0) / 1e6,
            "span_id": args.pop("span_id", None),
            "parent_id": args.pop("parent_id", None),
            "lane": ev.get("tid", 0),
            "attrs": args,
        })
    meta = dict(payload.get("otherData") or {})
    for name, value in (meta.pop("counters", None) or {}).items():
        records.append({"type": "counter", "name": name, "value": value})
    return records, meta


def _fmt_s(v: float) -> str:
    if v >= 1.0:
        return f"{v:.3f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.1f}us"


def span_tree(records: list[dict]) -> list[str]:
    """Aggregate spans by name-path (parent chain) -> indented table."""
    spans = [r for r in records if r.get("type", "span") == "span"]
    by_id = {s["span_id"]: s for s in spans if s.get("span_id") is not None}

    def path(s: dict) -> tuple[str, ...]:
        names, seen = [], set()
        while s is not None and s["span_id"] not in seen:
            seen.add(s["span_id"])
            names.append(s["name"])
            s = by_id.get(s.get("parent_id"))
        return tuple(reversed(names))

    agg: dict[tuple[str, ...], list[float]] = {}
    for s in spans:
        agg.setdefault(path(s), []).append(float(s.get("dur_s", 0.0)))
    lines = []
    width = max((2 * (len(p) - 1) + len(p[-1]) for p in agg), default=4)
    header = f"{'span':<{width}}  {'count':>5}  {'total':>9}  {'mean':>9}"
    lines.append(header)
    lines.append("-" * len(header))
    for p in sorted(agg):
        durs = agg[p]
        label = "  " * (len(p) - 1) + p[-1]
        lines.append(
            f"{label:<{width}}  {len(durs):>5}  {_fmt_s(sum(durs)):>9}  "
            f"{_fmt_s(sum(durs) / len(durs)):>9}")
    return lines


def report(path: str) -> str:
    records, meta = load_records(path)
    spans = [r for r in records if r.get("type", "span") == "span"]
    counters = [r for r in records if r.get("type") == "counter"]
    out = [f"trace: {path}  ({len(spans)} spans)"]
    if meta:
        prov = ", ".join(
            f"{k}={meta[k]}" for k in
            ("git_sha", "jax_version", "backend", "device_kind")
            if k in meta)
        if prov:
            out.append(f"provenance: {prov}")
        if meta.get("dropped_spans"):
            out.append(f"WARNING: flight recorder dropped "
                       f"{meta['dropped_spans']} spans (ring capacity)")
    out.append("")
    out.extend(span_tree(records) if spans else ["(no spans)"])
    if counters:
        out.append("")
        out.append("counters:")
        for c in counters:
            out.append(f"  {c['name']} = {c['value']}")
    acct = overlap_efficiency_from_spans(records)
    if acct:
        out.append("")
        out.append(
            f"overlap schedule ({acct['n_steps']} steps): "
            + "  ".join(f"{k}={_fmt_s(v)}" for k, v in acct["phase_s"].items())
            + f"  sum={_fmt_s(acct['sum_phases_s'])}"
            + f"  traced_wall={_fmt_s(acct['traced_wall_s'])}")
        out.append("  (efficiency = sum_phases / UNTRACED wall; traced walls "
                   "serialize at phase boundaries and cannot witness hiding)")
    out.append("")
    kind = meta.get("device_kind")
    out.append(f"attribution (measured vs roofline of "
               f"{kind or 'no recorded device: model columns empty'}):")
    out.append(render_attribution(attribution_report(records, kind)))
    return "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="render a repro.obs trace (span tree + attribution)")
    ap.add_argument("trace", nargs="?", default="artifacts/serve_trace.jsonl",
                    help="path to a .jsonl or .chrome.json trace "
                         "(default: %(default)s — where the traced serve "
                         "benchmark row exports)")
    args = ap.parse_args(argv)
    # the roofline model lowers kernels to count their work; on the host CPU,
    # so reading a trace never claims an accelerator another process may hold
    os.environ["JAX_PLATFORMS"] = "cpu"
    if not os.path.exists(args.trace):
        print(f"trace_report: no trace at {args.trace!r}", file=sys.stderr)
        return 1
    print(report(args.trace))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
