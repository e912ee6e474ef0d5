#!/usr/bin/env python
"""Dispatch profiler: count + per-dispatch overhead rows (``dispatch`` table).

The paper's PIUMA conclusion (§5.3) is that SU3_Bench's ceiling is pipeline
throughput — how fast work can be ISSUED, not how fast it runs.  On the
serving stack the analogous tax is the kernel dispatch: every launch pays a
fixed host-side cost that dominates at quick-mode lattice sizes.  This tool
measures that tax directly and lands it in ``BENCH_su3.json`` so the
trajectory is gated like every other row:

  dispatch_overhead_L{L}
      K single-step dispatches vs ONE fused(K) dispatch of the same K
      multiplies; the wall difference over K-1 is the per-dispatch overhead.
  megakernel_amortization_L{L}
      a SLOTS-slot table advanced one iteration as SLOTS single-lattice
      dispatches (the per-chain continuous path) vs ONE batched megakernel
      dispatch — the dispatch-count collapse the slot-table serving mode
      banks every iteration.

All timing runs through the ``repro.obs`` tracer — every rep is a
``profile.dispatch`` span on the same monotonic clock and span schema the
serving stack emits, and the table rows are derived from those spans
(``--trace PATH`` exports them as flat JSONL for ``scripts/trace_report.py``
or, with a ``.json`` suffix, as Chrome trace-event JSON).

Usage (wired into scripts/smoke.sh quick mode):

    PYTHONPATH=src python scripts/profile_dispatch.py --quick --json BENCH_su3.json
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

import jax.numpy as jnp

from repro.core.su3.engine import EngineConfig, SU3Engine
from repro.core.su3.layouts import Layout
from repro.obs import Tracer

SLOTS = 4
FUSED_K = 4
TILE = 128

# One clock, one span schema: every timed rep is a span on this tracer, and
# the table rows below are reductions over those spans.
TRACER = Tracer(enabled=True, capacity=4096)


def _median_wall(fn, reps: int, label: str, **attrs) -> float:
    spans = []
    for _ in range(reps):
        with TRACER.span("profile.dispatch", label=label, **attrs) as sp:
            fn()
        spans.append(sp)
    return float(statistics.median(s.dur_s for s in spans))


def dispatch_overhead_row(L: int, k: int = FUSED_K, reps: int = 5) -> dict:
    """K dispatched single steps vs one fused(K) dispatch (engine protocol)."""
    cfg = EngineConfig(L=L, dtype="float32", variant="pallas",
                       layout=Layout.SOA, tile=TILE, iterations=1, warmups=1)
    engine = SU3Engine(cfg)
    cmp = engine.compare_fused(k=k, reps=reps)
    per_dispatch_s = max(cmp["dispatched_s"] - cmp["fused_s"], 0.0) / (k - 1)
    return {
        "name": f"dispatch_overhead_L{L}",
        "L": L,
        "k": k,
        "dispatches_chained": k,
        "dispatches_fused": 1,
        "chained_s": round(cmp["dispatched_s"], 6),
        "fused_s": round(cmp["fused_s"], 6),
        "per_dispatch_overhead_us": round(per_dispatch_s * 1e6, 1),
        "fused_speedup": round(cmp["fused_speedup"], 3),
        "GFLOPS": cmp["result"].row()["GFLOPS"],  # fused per-multiply GF/s
        "verified": cmp["result"].verified,
    }


def megakernel_amortization_row(L: int, slots: int = SLOTS, reps: int = 5) -> dict:
    """SLOTS single-lattice dispatches vs ONE megakernel dispatch per
    iteration, on identical slot data — the serving-layer collapse."""
    cfg = EngineConfig(L=L, dtype="float32", variant="pallas",
                       layout=Layout.SOA, tile=TILE, iterations=1, warmups=1)
    plan = SU3Engine(cfg).plan
    rng = np.random.default_rng(0)
    S = plan.padded_sites
    a = rng.standard_normal((slots, S, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((slots, 4, 3, 3, 2)).astype(np.float32)
    import jax
    a_phys = jax.vmap(plan.codec.pack)(
        jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64))
    b_p = jax.vmap(plan.codec.pack_b)(
        jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64))
    ones = jnp.ones((slots,), jnp.int32)
    mega = plan.fused_batched_step(slots, max_k=1)
    # the megakernel donates its slot table: it advances its own copy, the
    # way the serving loop rebinds its table every iteration
    table = jnp.copy(a_phys)

    def per_chain():
        outs = [plan.step(a_phys[s], b_p[s]) for s in range(slots)]
        outs[-1].block_until_ready()

    def megakernel():
        nonlocal table
        table = mega(table, b_p, ones)
        table.block_until_ready()

    per_chain()  # warm both compiled shapes before timing
    megakernel()
    chain_s = _median_wall(per_chain, reps, "per_chain", L=L, slots=slots)
    mega_s = _median_wall(megakernel, reps, "megakernel", L=L, slots=slots)
    useful_flops = 864.0 * (L**4) * slots
    return {
        "name": f"megakernel_amortization_L{L}",
        "L": L,
        "slots": slots,
        "dispatches_per_iter_chains": slots,
        "dispatches_per_iter_megakernel": 1,
        "chains_s": round(chain_s, 6),
        "megakernel_s": round(mega_s, 6),
        "dispatch_amortization_speedup": round(chain_s / max(mega_s, 1e-9), 3),
        "per_dispatch_overhead_us": round(
            max(chain_s - mega_s, 0.0) / (slots - 1) * 1e6, 1),
        "GFLOPS": round(useful_flops / mega_s / 1e9, 3),
    }


def run(quick: bool = True) -> list[dict]:
    Ls = (2, 4) if quick else (4, 8)
    rows = []
    for L in Ls:
        rows.append(dispatch_overhead_row(L))
        rows.append(megakernel_amortization_row(L))
    return rows


def merge_into_artifact(rows: list[dict], path: str) -> None:
    """Land the ``dispatch`` table inside the benchmark artifact (creating a
    minimal payload when the harness has not run yet).  The provenance block
    is stamped if absent so a standalone profiler artifact still passes the
    bench_diff provenance gate."""
    payload = {"schema": "su3-bench-rows/v1", "tables": {}}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload.setdefault("tables", {})["dispatch"] = rows
    if "provenance" not in payload:
        from repro.obs import provenance_block

        payload["provenance"] = provenance_block()
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default="",
                    help="merge rows into this BENCH_su3.json artifact")
    ap.add_argument("--trace", default="",
                    help="export the profiling spans (.jsonl flat / "
                         ".json Chrome trace-event)")
    args = ap.parse_args(argv)
    rows = run(quick=args.quick)
    for r in rows:
        print(r)
    if args.json:
        merge_into_artifact(rows, args.json)
        print(f"# merged dispatch table into {args.json}", file=sys.stderr)
    if args.trace:
        from repro.obs import provenance_block

        meta = provenance_block()
        if args.trace.endswith(".jsonl"):
            n = TRACER.to_jsonl(args.trace, metadata=meta)
        else:
            n = TRACER.to_chrome_trace(args.trace, metadata=meta)
        print(f"# wrote {n} records to {args.trace}", file=sys.stderr)
    bad = [r for r in rows if "verified" in r and not r["verified"]]
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
