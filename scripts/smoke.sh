#!/usr/bin/env bash
# Quick-mode smoke: fast-tier suite + machine-readable benchmark rows.
#
#   scripts/smoke.sh            # fast tests (-m "not slow") + benchmarks
#   scripts/smoke.sh --full     # also run the slow tier (serving/e2e
#                               # tests — the ~12-minute tail)
#   scripts/smoke.sh --no-bench # tests only
#
# The tier-1 gate (`python -m pytest -x -q`, no marker filter) still runs
# everything; smoke iterations default to the fast tier so the slow serving
# suites no longer gate every edit loop.
#
# Writes BENCH_su3.json in the repo root so the perf trajectory is
# comparable across PRs (schema: su3-bench-rows/v1).  The stencil table
# (benchmarks/stencil.py) rides in benchmarks.run alongside the rest.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

RUN_FULL=0
RUN_BENCH=1
for arg in "$@"; do
  case "$arg" in
    --full) RUN_FULL=1 ;;
    --no-bench) RUN_BENCH=0 ;;
    *) echo "unknown flag: $arg" >&2; exit 2 ;;
  esac
done

echo "== compressed-gauge spot check (reconstruct accuracy + depth-2 identity) =="
# Seconds, not minutes: ONE reconstruct bit-accuracy check and ONE
# depth-2-vs-two-exchange identity check so a broken compression path
# surfaces before the full fast tier spins up.  The exhaustive sweeps
# (layout x dtype x tile property tests, multi-host subprocess identity,
# autotune sweeps) stay in the fast/slow pytest tiers below.
python -m pytest -x -q \
  tests/test_compression.py::test_compressed_multiply_matches_full_kernel_on_su3 \
  "tests/test_compression.py::test_stencil_depth2_single_host_bit_identical[two_row]"

echo "== chaos spot check (storm zero-lost + same-seed fault reproduction) =="
# Seconds, not minutes: ONE seeded fault-storm run through the serving
# stack (every request must resolve, retried results bitwise clean) and
# ONE FaultPlan determinism check, so a broken robustness seam surfaces
# before the full tiers.  The full chaos matrix (-m chaos) rides in the
# fast tier below.
python -m pytest -x -q \
  tests/test_robustness.py::test_storm_zero_lost_and_bitwise_clean \
  tests/test_chaos.py::test_same_seed_reproduces_fault_log

echo "== tenancy spot check (deficit-fair turns + brownout replay determinism) =="
# Seconds, not minutes: ONE service-level fairness check (two tenants'
# bulk queues earn equal DRR turns however lopsided the backlogs) and ONE
# brownout-ladder signature replay check, so a broken scheduler or a
# non-deterministic overload ladder surfaces before the full tiers.  The
# full multi-tenant matrix (-m tenancy) rides in the fast tier below.
python -m pytest -x -q \
  tests/test_tenancy.py::test_deficit_fair_turns_across_tenants_in_service \
  tests/test_tenancy.py::test_brownout_signature_is_replay_deterministic

echo "== CG solver spot check (convergence pin + fused bit-identity) =="
# The flagship solve, in seconds: ONE end-to-end convergence check against
# the independent oracle and ONE fused-vs-composed bit-identity check, so
# a numerically broken solver surfaces before the full tiers and the
# benchmark harness spin up.  The full grid (layout x dtype x compression
# property tests, multi-host subprocess identity, serving mixes) stays in
# the pytest tiers below.
python -m pytest -x -q \
  tests/test_cg_solve.py::test_cg_converges_and_solves_the_system \
  tests/test_cg_solve.py::test_fused_composed_bit_identical_f32

echo "== fast tier (-m 'not slow') =="
python -m pytest -x -q -m "not slow"

if [[ "$RUN_FULL" == 1 ]]; then
  echo "== slow tier (-m slow: serving/e2e) =="
  python -m pytest -x -q -m slow
fi

if [[ "$RUN_BENCH" == 1 ]]; then
  echo "== fig7 multi-controller dryrun (2 controllers, divergence gate) =="
  # Two identical controller processes run the strong-scaling curve through
  # the real (host, device) MeshSpec plan path; the launcher exits non-zero
  # if any point's result lattice diverges from the single-host reference
  # on any controller.
  python -m repro.launch.dryrun --su3-fig7 \
    --L 4 --device-counts 1,2 --hosts 2 --controllers 2 --iterations 1 \
    > /dev/null

  echo "== quick benchmarks incl. stencil table (BENCH_su3.json) =="
  python -m benchmarks.run --quick --json BENCH_su3.json
  echo "== dispatch profiler (dispatch table -> BENCH_su3.json) =="
  python scripts/profile_dispatch.py --quick --json BENCH_su3.json
  echo "== trace report (artifacts/serve_trace from the traced serve row) =="
  # benchmarks.run's serve section exported the trace pair into the
  # gitignored artifacts/ dir; the report must render (span tree +
  # attribution) or the obs layer broke
  python scripts/trace_report.py artifacts/serve_trace.jsonl > /dev/null
  python scripts/trace_report.py artifacts/serve_trace.chrome.json | tail -8
  echo "== bench diff vs last committed artifact (>15% GFLOPS drop fails) =="
  # BENCH_DIFF_THRESHOLD loosens the gate on noisy shared dev hosts; flagged
  # rows are re-measured (median of 3) by scripts/bench_diff.py before the
  # gate fails, so residual failures are real regressions, not timer noise.
  # Rows present on only one side are named WARNINGs, never silent skips.
  # The CG gate rides in the same call: cg_residual_vs_time must converge,
  # and may not need >10% more iterations to the committed tol.  The chaos
  # gate does too: the serve_chaos storm row must report zero lost
  # requests, bitwise-clean successes, and same-seed fault reproduction.
  # So does the tenancy gate: the serve_tenancy row must hold the latency
  # p99 ceiling under the bulk flood, clear the Jain fairness floor, and
  # reproduce its brownout transition log from the same seed.
  python scripts/bench_diff.py --current BENCH_su3.json --baseline git:HEAD \
    --threshold "${BENCH_DIFF_THRESHOLD:-0.15}"
fi
