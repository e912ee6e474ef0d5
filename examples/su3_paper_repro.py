"""Reproduce the paper's experiment structure end-to-end (CPU-scaled).

Walks the paper's §4 narrative: baseline variants (Table 2), placement
policies (Table 3/5/6), VersionX, explicit GEMM (Fig 9), and prints the
three-term rooflines for Xeon / PIUMA / v5e (Table 1, §5.3, Fig 10).

    PYTHONPATH=src python examples/su3_paper_repro.py [--L 8]
"""
import argparse

from benchmarks import (
    fig9_gemm_vs_dot, fig10_arch_compare, table1_roofline,
    table2_variants, table3_placement,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--L", type=int, default=8)
    args = ap.parse_args()

    print("== Table 1: roofline ladders (Xeon + v5e) ==")
    for r in table1_roofline.xeon_ladder()[:3] + table1_roofline.v5e_ladder():
        print("  ", r)
    print("== Table 2: variant baselines ==")
    for r in table2_variants.run(L=args.L, iters=(1, 5)):
        print("  ", {k: r[k] for k in ("name", "GFLOPS", "GBYTES", "verified")})
    print("== Table 3: placement (NUMA/first-touch analog) ==")
    for r in table3_placement.run(L=args.L):
        print("  ", {k: r[k] for k in ("name", "GFLOPS", "init_s", "scatter_s")})
    print("== Fig 9: explicit GEMM vs compiler dot ==")
    for r in fig9_gemm_vs_dot.run(sizes=(args.L,)):
        print("  ", {k: r[k] for k in ("name", "GFLOPS", "GBYTES")})
    print("== Fig 10: cross-architecture bound ==")
    for r in fig10_arch_compare.run(L=args.L):
        print("  ", r)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
