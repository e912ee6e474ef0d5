"""Chaos verification benchmark (the ``chaos`` section): a seeded fault
storm over a mixed request population, gated on the robustness contract.

One :func:`repro.chaos.storm` plan (dispatch fail/delay + kernel NaN/Inf
poison + warm-pool build failures, bounded by ``max_fires`` so the storm
*ends* and recovery is observable) is driven over a closed-loop mix of
multiply requests plus one CG solve, against the same service config that
serves the clean baseline.  The row records — and ``main``/
``scripts/bench_diff.py`` gate on — the ISSUE 9 acceptance points:

  zero lost requests    every submitted request resolves: a result, a
                        structured error (RetriesExhausted / CGDiverged),
                        or a structured timeout — nothing hangs, nothing
                        silently drops;
  bitwise clean         every request that *succeeded* under the storm
                        returns a result bitwise identical to the
                        fault-free baseline (retried dispatches re-run
                        the same compiled path on the same inputs);
  bounded p99           the storm may inflate tail latency by retries and
                        backoff, but only boundedly (default ceiling
                        ``P99_INFLATION_CEILING`` x the clean p99);
  recovery              seconds from each injected fault to the next
                        completed request — the storm's max_fires bound
                        makes "the service came back" a measurable number;
  same-seed reproduction  the identical replay under ``FaultPlan.reset()``
                        (same seed, same specs) fires the same faults in
                        the same per-site order — a chaos failure is a
                        bug report, not a shrug.

Fault provenance rides in the row: ``plan.describe()`` (seed + per-site
schedule) plus the full fired log, so any artifact number produced under
injection names the exact faults behind it.

Standalone CLI:

    PYTHONPATH=src python -m benchmarks.serve_chaos --quick
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax.numpy as jnp

from repro.chaos import FaultPlan, storm
from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service
from repro.serve.su3.robustness import RequestFailure, RetryPolicy

TILE = 128
P99_INFLATION_CEILING = 25.0  # chaos p99 may cost retries, not a meltdown
# Backoffs far below one dispatch time: retries rejoin the queue by the
# next step, so the storm's ask schedule (and therefore its fired-fault
# log) is reproducible run-to-run — the same-seed gate depends on it.
RETRY = RetryPolicy(max_retries=6, base_s=1e-6, cap_s=1e-5, jitter=0.2,
                    budget=512)


def _random_request(rng: np.random.Generator, n_sites: int):
    a = rng.standard_normal((n_sites, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((4, 3, 3, 2)).astype(np.float32)
    return (
        jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64),
        jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64),
    )


def _service(L: int, faults: FaultPlan | None) -> SU3Service:
    return SU3Service(ServiceConfig(
        autotune=False, tile=min(TILE, L**4), faults=faults, retry=RETRY,
        solve_iters_per_step=4,
        batcher=BatcherConfig(
            max_batch=4, warm_batch_sizes=(1, 2, 4), max_queue_depth=256,
        ),
    ))


def _replay(svc: SU3Service, population: list, solve_problem, tol: float,
            max_iters: int) -> dict:
    """Submit the whole mix up-front, drain, and account every request.

    Returns resolved results keyed by submission index (arrays or
    structured failure objects), per-fault recovery samples, and the
    service metrics snapshot.  Closed-loop submission keeps the dispatch
    schedule deterministic, which is what makes the same-seed fired-log
    comparison an end-to-end gate rather than a unit test.
    """
    ids = []
    if solve_problem is not None:
        u, b = solve_problem
        ids.append(("solve", svc.submit_solve(u, b, tol=tol,
                                              max_iters=max_iters)))
    for a, b in population:
        ids.append(("multiply", svc.submit(a, b, k=2)))

    resolved: dict[int, object] = {}
    pending_fault_t: list[float] = []
    recovery: list[float] = []
    t0 = time.perf_counter()
    steps = 0
    while svc.pending() and steps < 20_000:
        steps += 1
        n_faults0 = svc.metrics.faults_injected
        svc.step()
        now = time.perf_counter()
        pending_fault_t.extend([now] * (svc.metrics.faults_injected - n_faults0))
        ready = svc.pop_ready()
        if ready:
            resolved.update(ready)
            if pending_fault_t:
                recovery.extend(now - t for t in pending_fault_t)
                pending_fault_t.clear()
    resolved.update(svc.pop_ready())
    wall = time.perf_counter() - t0
    return {
        "ids": ids,
        "resolved": resolved,
        "recovery_s": recovery,
        "unrecovered_faults": len(pending_fault_t),
        "wall_s": wall,
        "snapshot": svc.metrics.snapshot(),
    }


def _storm_plan(seed: int) -> FaultPlan:
    return storm(seed, dispatch_p=0.35, kernel_p=0.35, pool_p=0.5,
                 max_fires=4, delay_s=0.002)


def _log_key(entry: dict) -> tuple:
    # ctx is call-site metadata (host ids, kinds) and seq is the global
    # interleave; the determinism contract is per-site: same seed + same
    # per-site ask schedule => same (site, action, site_seq) sequence
    return (entry["site"], entry["action"], entry["site_seq"])


def fault_storm(L: int = 2, n_multiply: int = 20, seed: int = 0) -> dict:
    """The ``serve_chaos`` row: baseline replay, storm replay, repro replay."""
    from benchmarks.cg_solve import _problem

    rng = np.random.default_rng(seed)
    n_sites = L**4
    population = [_random_request(rng, n_sites) for _ in range(n_multiply)]
    solve_problem = _problem(L)
    tol, max_iters = 1e-6, 64

    def run_one(faults: FaultPlan | None) -> tuple[dict, SU3Service]:
        svc = _service(L, faults)
        svc.warm((L,), ks=(2,), batch_sizes=svc.cfg.batcher.warm_batch_sizes)
        svc.metrics.reset()
        return _replay(svc, population, solve_problem, tol, max_iters), svc

    base, _ = run_one(None)
    plan = _storm_plan(seed)
    chaos, chaos_svc = run_one(plan)
    replay_plan = plan.reset()
    rerun, _ = run_one(replay_plan)

    # -- zero lost: every id resolved as a result or a structured failure --
    def account(run: dict) -> tuple[int, int, dict[str, int], bool]:
        ok = failed = 0
        by_type: dict[str, int] = {}
        lost = False
        for _kind, rid in run["ids"]:
            out = run["resolved"].get(rid, None)
            if out is None:
                lost = True
            elif isinstance(out, Exception):
                if not isinstance(out, (RequestFailure, RuntimeError)):
                    lost = True  # an unstructured escape is a lost request
                failed += 1
                t = type(out).__name__
                by_type[t] = by_type.get(t, 0) + 1
            else:
                ok += 1
        return ok, failed, by_type, lost

    ok_n, failed_n, failed_by_type, lost = account(chaos)
    ok2, failed2, _, lost2 = account(rerun)
    zero_lost = (not lost) and (not lost2)

    # -- bitwise identity: chaos successes vs the fault-free baseline ------
    clean_bitwise = True
    compared = 0
    for (_k, rid_b), (_k2, rid_c) in zip(base["ids"], chaos["ids"]):
        out_b = base["resolved"].get(rid_b)
        out_c = chaos["resolved"].get(rid_c)
        if isinstance(out_b, Exception) or isinstance(out_c, Exception):
            continue
        if out_b is None or out_c is None:
            continue
        compared += 1
        if not bool(jnp.array_equal(out_b, out_c)):
            clean_bitwise = False

    # -- same-seed reproduction: fired logs agree per site -----------------
    log1 = [_log_key(e) for e in plan.log()]
    log2 = [_log_key(e) for e in replay_plan.log()]
    same_seed = sorted(log1) == sorted(log2) and len(log1) > 0

    p99_base = base["snapshot"]["latency_p99_ms"]
    p99_chaos = chaos["snapshot"]["latency_p99_ms"]
    inflation = p99_chaos / max(p99_base, 1e-9)
    recovery = chaos["recovery_s"]
    snap = chaos["snapshot"]
    return {
        "name": "serve_chaos",
        "L": L,
        "seed": seed,
        "n_multiply": n_multiply,
        "n_solve": 1,
        "tol": tol,
        "max_iters": max_iters,
        "storm": plan.describe(),
        "faults_fired": plan.fired,
        "fired_by_site": plan.fired_by_site(),
        "fault_log": plan.log(),
        "completed_ok": ok_n,
        "failed_structured": failed_n,
        "failed_by_type": failed_by_type,
        "zero_lost": zero_lost,
        "compared_results": compared,
        "clean_results_bitwise": clean_bitwise,
        "latency_p99_ms_baseline": p99_base,
        "latency_p99_ms_chaos": p99_chaos,
        "p99_inflation": round(inflation, 3),
        "p99_inflation_bounded": inflation <= P99_INFLATION_CEILING,
        "recovery_max_s": round(max(recovery), 6) if recovery else 0.0,
        "recovery_mean_s": round(float(np.mean(recovery)), 6) if recovery else 0.0,
        "recovered_faults": len(recovery),
        "unrecovered_faults": chaos["unrecovered_faults"],
        "same_seed_reproduces": same_seed,
        "rerun_completed_ok": ok2,
        "rerun_failed_structured": failed2,
        "retries": snap["retries"],
        "retries_exhausted": snap["retries_exhausted"],
        "timeouts": snap["timeouts"],
        "shed": snap["shed"],
        "quarantines": snap["quarantines"],
        "wall_s_baseline": round(base["wall_s"], 3),
        "wall_s_chaos": round(chaos["wall_s"], 3),
        "health": chaos_svc.health.snapshot(),
    }


def gate_problems(row: dict) -> list[str]:
    """The acceptance checks ``main`` and bench_diff's chaos gate share."""
    problems = []
    if row.get("error"):
        return [f"serve_chaos: row errored: {row['error']}"]
    if row.get("faults_fired", 0) <= 0:
        problems.append("serve_chaos: the storm fired no faults — the row "
                        "proves nothing")
    if row.get("zero_lost") is not True:
        problems.append("serve_chaos: LOST REQUESTS — a submitted request "
                        "resolved as neither result nor structured failure")
    if row.get("clean_results_bitwise") is not True:
        problems.append("serve_chaos: a request that succeeded under the "
                        "storm is NOT bitwise identical to the fault-free "
                        "baseline")
    if row.get("same_seed_reproduces") is not True:
        problems.append("serve_chaos: the same seed did NOT reproduce the "
                        "same fault sequence")
    if row.get("p99_inflation_bounded") is not True:
        problems.append(
            f"serve_chaos: p99 inflation {row.get('p99_inflation')}x exceeds "
            f"the {P99_INFLATION_CEILING}x ceiling")
    return problems


def run(quick: bool = True, seed: int = 0) -> list[dict]:
    """The ``chaos`` benchmark section (wired into benchmarks.run)."""
    n = 12 if quick else 32
    return [fault_storm(L=2, n_multiply=n, seed=seed)]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    rows = run(quick=args.quick, seed=args.seed)
    ok = True
    for r in rows:
        print(r)
        for p in gate_problems(r):
            print(f"FAIL: {p}", file=sys.stderr)
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
