"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness contract.
``us_per_call`` is the best iteration time where measured (engine rows) and
empty for analytic tables; ``derived`` carries the table-specific payload.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json [PATH]] [--seed N]

``--seed`` re-keys the seeded sections (the chaos fault storm and the
tenancy mix) so their deterministic schedules can be varied without
touching the timing tables.

``--json`` additionally writes a machine-readable ``BENCH_su3.json`` (all
rows, grouped per table, with GFLOPS/GBYTES where measured) so the perf
trajectory is tracked across PRs; ``scripts/smoke.sh`` wires it into the
quick-mode smoke run.

Every artifact carries a ``provenance`` block (git sha, jax/jaxlib
versions, backend, device kind, XLA flags, autotune cache schema —
``repro.obs.provenance_block``): numbers without the environment that
produced them are not comparable, and ``scripts/bench_diff.py`` refuses a
diff whose current side lacks the block or whose jax/backend pair changed
without a re-baseline note (``REPRO_BENCH_REBASELINE="why"``).
"""
from __future__ import annotations

import json
import sys

DEFAULT_JSON = "BENCH_su3.json"


def _emit(rows: list[dict], collected: dict[str, list[dict]], table: str) -> None:
    collected[table] = [dict(r) for r in rows]
    for r in rows:
        r = dict(r)
        name = r.pop("name", "unnamed")
        us = r.pop("us_per_call", None)
        if us is None and "best_s" in r:
            us = round(r["best_s"] * 1e6, 1)
        derived = json.dumps(r, default=str)
        print(f"{name},{us if us is not None else ''},{derived}")


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    quick = "--quick" in argv
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        nxt = argv[i + 1] if i + 1 < len(argv) else None
        json_path = nxt if nxt and not nxt.startswith("--") else DEFAULT_JSON
    seed = 0  # seeded sections (chaos storm, tenancy mix) key off this
    if "--seed" in argv:
        i = argv.index("--seed")
        if i + 1 < len(argv):
            seed = int(argv[i + 1])

    from repro.compile_cache import enable_compile_cache

    from benchmarks import (
        cg_solve, fig7_strong_scaling, fig9_gemm_vs_dot, fig10_arch_compare,
        lm_step, serve_chaos, serve_tenancy, serve_traffic, stencil,
        table1_roofline, table2_variants, table3_placement,
    )

    enable_compile_cache()
    collected: dict[str, list[dict]] = {}
    failed: list[str] = []
    tables = [
        ("table1_roofline", lambda: table1_roofline.run()),
        ("table2_variants", lambda: table2_variants.run(
            L=8 if not quick else 4, iters=(1, 5) if not quick else (1, 4))),
        ("table3_placement", lambda: table3_placement.run(L=8 if not quick else 4)),
        ("fig7_strong_scaling", lambda: fig7_strong_scaling.run(
            L=8 if not quick else 4,
            device_counts=(1, 2, 4) if not quick else (1, 2))),
        ("fig9_gemm_vs_dot", lambda: fig9_gemm_vs_dot.run(
            sizes=(4, 8) if not quick else (4,))),
        ("fig10_arch_compare", lambda: fig10_arch_compare.run(L=8 if not quick else 4)),
        ("lm_step", lambda: lm_step.run()),
        ("serve", lambda: serve_traffic.run(quick=quick)),
        ("chaos", lambda: serve_chaos.run(quick=quick, seed=seed)),
        ("tenancy", lambda: serve_tenancy.run(quick=quick, seed=seed)),
        ("stencil", lambda: stencil.run(quick=quick)),
        ("cg", lambda: cg_solve.run(quick=quick)),
    ]
    for table, fn in tables:
        # one broken table must not take the other rows or the JSON
        # artifact down with it
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            rows = [{"name": f"{table}_error", "error": f"{type(e).__name__}: {e}"[:300]}]
            failed.append(table)
        _emit(rows, collected, table)

    if json_path:
        from repro.obs import provenance_block

        payload = {
            "schema": "su3-bench-rows/v1",
            "quick": quick,
            "provenance": provenance_block(),
            "tables": collected,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, default=str)
        print(f"# wrote {json_path}", file=sys.stderr)
    if failed:
        raise SystemExit(f"tables with errors: {', '.join(failed)}")


if __name__ == "__main__":
    main()
