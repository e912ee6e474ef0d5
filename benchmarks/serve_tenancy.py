"""Multi-tenant SLO benchmark (the ``tenancy`` section): an adversarial
tenant mix under overload, gated on the fairness + isolation contract.

Three tenants share one service: ``steady`` submits a fixed stream of
latency-class multiplies (the interactive tenant whose tail the gate
protects), ``burst`` floods the bulk lane with several times the queue's
fair share plus one CG solve, and ``drip`` submits a small bulk batch that
a FIFO scheduler would starve behind the flood.  The same population is
replayed four ways — unloaded latency baseline, loaded clean run, loaded
run under a seeded :func:`repro.chaos.storm`, and a same-seed storm replay
— and the row records the ISSUE 10 acceptance points:

  bounded latency tail  the steady tenant's latency-class p99 under the
                        bulk burst stays within ``LATENCY_P99_CEILING`` x
                        its unloaded p99 (deficit-weighted turns are what
                        make this hold);
  fairness              Jain's index over per-bulk-tenant delivered
                        completions, sampled the moment the smaller bulk
                        tenant finishes — a fair scheduler serves both at
                        the same rate however lopsided the backlogs, a
                        FIFO drain scores well under ``JAIN_FLOOR``;
  brownout provenance   the flood must actually climb the ladder (>= 1
                        transition) and the same seed must reproduce the
                        exact transition log (turn, from, to) under the
                        storm replay;
  zero lost             every submission resolves: a result, a structured
                        failure, or a deterministic front-door rejection
                        (quota / queue budget) — nothing hangs;
  bitwise clean         multiplies that succeed under the storm match the
                        clean loaded run bit for bit (solve results are
                        excluded: rung-2 degradation may legitimately
                        re-chunk the iteration schedule).

Quota provenance rides in the row (``quota_rejected_by_tenant``): the
burst tenant's token bucket is sized below its submission count, so the
front door provably meters — with ``rate_per_s=0`` the budget is pure
burst and the rejection count is deterministic.

Standalone CLI:

    PYTHONPATH=src python -m benchmarks.serve_tenancy --quick
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import jax.numpy as jnp

from repro.chaos import FaultPlan, storm
from repro.serve.su3 import (
    AutoscaleConfig, BatcherConfig, BrownoutConfig, ServiceConfig,
    SU3Service, TenantQuota,
)
from repro.serve.su3.robustness import RequestFailure, RetryPolicy

TILE = 128
LATENCY_P99_CEILING = 2.0  # loaded latency p99 vs unloaded (the SLO)
JAIN_FLOOR = 0.8  # min Jain index over per-bulk-tenant delivered work
# Same rationale as serve_chaos: backoffs far below one dispatch time keep
# the retry schedule (and the fired-fault log) reproducible run-to-run.
RETRY = RetryPolicy(max_retries=6, base_s=1e-6, cap_s=1e-5, jitter=0.2,
                    budget=512)

TENANT_LATENCY = "steady"
TENANT_BURST = "burst"
TENANT_DRIP = "drip"


def _random_request(rng: np.random.Generator, n_sites: int):
    a = rng.standard_normal((n_sites, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((4, 3, 3, 2)).astype(np.float32)
    return (
        jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64),
        jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64),
    )


def _service(L: int, faults: FaultPlan | None, max_queue_depth: int,
             quota_burst: int) -> SU3Service:
    return SU3Service(ServiceConfig(
        autotune=False, tile=min(TILE, L**4), faults=faults, retry=RETRY,
        hosts=2, solve_iters_per_step=4,
        quotas={TENANT_BURST: TenantQuota(rate_per_s=0.0,
                                          burst=float(quota_burst))},
        autoscale=AutoscaleConfig(enabled=True, min_hosts=1, grow_turns=2,
                                  shrink_turns=6),
        brownout=BrownoutConfig(enter_pressure=0.5, exit_pressure=0.2,
                                sustain_turns=2, exit_turns=4),
        batcher=BatcherConfig(
            max_batch=4, warm_batch_sizes=(1, 2, 4),
            max_queue_depth=max_queue_depth,
        ),
    ))


def _bulk_count(svc: SU3Service, tenant: str) -> int:
    res = svc.metrics.latencies_by_class.get(f"{tenant}/bulk")
    return res.count if res is not None else 0


def _replay(svc: SU3Service, submit_mix, checkpoint_at: int) -> dict:
    """Submit the whole mix up-front, drain, and account every request.

    ``submit_mix(svc)`` returns the submission ledger
    ``[(kind, tenant, req_id-or-None)]`` — a None id is a deterministic
    front-door rejection (quota or queue budget), accounted separately
    from in-system requests.  The fairness checkpoint samples per-bulk-
    tenant completion counts the first time the drip tenant has
    ``checkpoint_at`` completions — i.e. while the burst backlog is still
    contending — which is the window where fair and FIFO schedules differ.
    """
    ids = submit_mix(svc)
    resolved: dict[int, object] = {}
    checkpoint: dict[str, int] | None = None
    t0 = time.perf_counter()
    steps = 0
    while svc.pending() and steps < 20_000:
        steps += 1
        svc.step()
        ready = svc.pop_ready()
        if ready:
            resolved.update(ready)
        if checkpoint is None and _bulk_count(svc, TENANT_DRIP) >= checkpoint_at:
            checkpoint = {t: _bulk_count(svc, t)
                          for t in (TENANT_BURST, TENANT_DRIP)}
    resolved.update(svc.pop_ready())
    if checkpoint is None:  # drip never finished — score the final counts
        checkpoint = {t: _bulk_count(svc, t)
                      for t in (TENANT_BURST, TENANT_DRIP)}
    return {
        "ids": ids,
        "resolved": resolved,
        "checkpoint": checkpoint,
        "steps": steps,
        "wall_s": time.perf_counter() - t0,
        "snapshot": svc.metrics.snapshot(),
        "brownout_signature": [list(t) for t in svc._brownout.signature()],
    }


def _storm_plan(seed: int) -> FaultPlan:
    return storm(seed, dispatch_p=0.3, kernel_p=0.3, pool_p=0.5,
                 max_fires=3, delay_s=0.001)


def _log_key(entry: dict) -> tuple:
    # per-site determinism contract, same as serve_chaos
    return (entry["site"], entry["action"], entry["site_seq"])


def jain_index(xs: list[float]) -> float:
    """Jain's fairness index: 1.0 = perfectly even, 1/n = one tenant
    took everything.  0.0 when nothing was delivered at all."""
    total = float(sum(xs))
    if total <= 0.0:
        return 0.0
    return total * total / (len(xs) * float(sum(x * x for x in xs)))


def tenancy_mix(L: int = 2, n_latency: int = 8, n_burst: int = 24,
                quota_burst: int = 20, n_drip: int = 6,
                max_queue_depth: int = 48, seed: int = 0) -> dict:
    """The ``serve_tenancy`` row: unloaded baseline, loaded clean run,
    loaded storm run, same-seed storm replay."""
    from benchmarks.cg_solve import _problem

    rng = np.random.default_rng(seed)
    n_sites = L**4
    latency_pop = [_random_request(rng, n_sites) for _ in range(n_latency)]
    burst_pop = [_random_request(rng, n_sites) for _ in range(n_burst)]
    drip_pop = [_random_request(rng, n_sites) for _ in range(n_drip)]
    solve_problem = _problem(L)
    tol, max_iters = 1e-6, 64

    def submit_loaded(svc: SU3Service) -> list:
        # the steady tenant is in residence when the flood arrives
        ids = [("multiply", TENANT_LATENCY,
                svc.submit(a, b, k=2, tenant=TENANT_LATENCY, slo="latency"))
               for a, b in latency_pop]
        u, bb = solve_problem
        ids.append(("solve", TENANT_BURST,
                    svc.submit_solve(u, bb, tol=tol, max_iters=max_iters,
                                     tenant=TENANT_BURST, slo="bulk")))
        ids.extend(("multiply", TENANT_BURST,
                    svc.submit(a, b, k=2, tenant=TENANT_BURST))
                   for a, b in burst_pop)
        ids.extend(("multiply", TENANT_DRIP,
                    svc.submit(a, b, k=2, tenant=TENANT_DRIP))
                   for a, b in drip_pop)
        return ids

    def submit_unloaded(svc: SU3Service) -> list:
        return [("multiply", TENANT_LATENCY,
                 svc.submit(a, b, k=2, tenant=TENANT_LATENCY, slo="latency"))
                for a, b in latency_pop]

    def run_one(faults: FaultPlan | None, submit_mix) -> dict:
        svc = _service(L, faults, max_queue_depth, quota_burst)
        svc.warm((L,), ks=(2,), batch_sizes=svc.cfg.batcher.warm_batch_sizes)
        # compile the solve path before timing: its one-off jit cost would
        # otherwise land on whichever latency requests are queued behind it
        u, bb = solve_problem
        rid = svc.submit_solve(u, bb, tol=1e-2, max_iters=8,
                               tenant=TENANT_BURST, slo="bulk")
        steps = 0
        while svc.pending() and steps < 1_000:
            steps += 1
            svc.step()
        svc.pop_ready()
        svc.metrics.reset()
        return _replay(svc, submit_mix, checkpoint_at=n_drip)

    unloaded = run_one(None, submit_unloaded)
    loaded = run_one(None, submit_loaded)
    plan = _storm_plan(seed)
    stormed = run_one(plan, submit_loaded)
    replay_plan = plan.reset()
    rerun = run_one(replay_plan, submit_loaded)

    # -- zero lost: every in-system id resolves; None ids are deterministic
    #    front-door rejections (quota / queue budget), counted separately --
    def account(run: dict) -> dict:
        ok = failed = rejected = 0
        lost = False
        for _kind, _tenant, rid in run["ids"]:
            if rid is None:
                rejected += 1
                continue
            out = run["resolved"].get(rid, None)
            if out is None:
                lost = True
            elif isinstance(out, Exception):
                if not isinstance(out, (RequestFailure, RuntimeError)):
                    lost = True  # an unstructured escape is a lost request
                failed += 1
            else:
                ok += 1
        return {"ok": ok, "failed": failed, "rejected": rejected,
                "lost": lost}
    acct_loaded = account(loaded)
    acct_storm = account(stormed)
    acct_rerun = account(rerun)
    zero_lost = not (acct_loaded["lost"] or acct_storm["lost"]
                     or acct_rerun["lost"])

    # -- bitwise: storm-run multiply successes vs the clean loaded run -----
    clean_bitwise = True
    compared = 0
    for (kind, _t, rid_a), (_k2, _t2, rid_b) in zip(loaded["ids"],
                                                    stormed["ids"]):
        if kind != "multiply" or rid_a is None or rid_b is None:
            continue
        out_a = loaded["resolved"].get(rid_a)
        out_b = stormed["resolved"].get(rid_b)
        if isinstance(out_a, Exception) or isinstance(out_b, Exception):
            continue
        if out_a is None or out_b is None:
            continue
        compared += 1
        if not bool(jnp.array_equal(out_a, out_b)):
            clean_bitwise = False

    # -- same-seed: fault log AND brownout transition log reproduce --------
    log1 = [_log_key(e) for e in plan.log()]
    log2 = [_log_key(e) for e in replay_plan.log()]
    same_seed = sorted(log1) == sorted(log2) and len(log1) > 0
    sig_reproduced = (stormed["brownout_signature"] == rerun["brownout_signature"]
                      and len(stormed["brownout_signature"]) > 0)

    # -- fairness + latency SLO --------------------------------------------
    jain = jain_index([float(v) for v in loaded["checkpoint"].values()])
    lat_key = f"{TENANT_LATENCY}/latency"
    p99_unloaded = unloaded["snapshot"]["latency_by_class_ms"].get(
        lat_key, {}).get("p99", 0.0)
    p99_loaded = loaded["snapshot"]["latency_by_class_ms"].get(
        lat_key, {}).get("p99", 0.0)
    inflation = p99_loaded / max(p99_unloaded, 1e-9)

    snap = loaded["snapshot"]
    return {
        "name": "serve_tenancy",
        "L": L,
        "seed": seed,
        "tenants": {
            TENANT_LATENCY: {"slo": "latency", "n": n_latency},
            TENANT_BURST: {"slo": "bulk", "n": n_burst + 1,
                           "quota_burst": quota_burst},
            TENANT_DRIP: {"slo": "bulk", "n": n_drip},
        },
        "max_queue_depth": max_queue_depth,
        "latency_p99_ms_unloaded": p99_unloaded,
        "latency_p99_ms_loaded": p99_loaded,
        "latency_inflation": round(inflation, 3),
        "latency_bounded": inflation <= LATENCY_P99_CEILING,
        "jain_fairness": round(jain, 4),
        "fairness_ok": jain >= JAIN_FLOOR,
        "fairness_checkpoint": loaded["checkpoint"],
        "per_class_latency_ms": snap["latency_by_class_ms"],
        "admitted_by_class": snap["admitted_by_class"],
        "shed_by_class": snap["shed_by_class"],
        "quota_rejected": snap["quota_rejected"],
        "quota_rejected_by_tenant": snap["quota_rejected_by_tenant"],
        "scale_ups": snap["scale_ups"],
        "scale_downs": snap["scale_downs"],
        "brownout_rung_turns": snap["brownout_rung_turns"],
        "brownout_transitions": snap["brownout_transitions"],
        "brownout_signature": loaded["brownout_signature"],
        "brownout_signature_reproduced": sig_reproduced,
        "brownout_degraded_solve_turns": snap["brownout_degraded_solve_turns"],
        "completed_ok": acct_loaded["ok"],
        "failed_structured": acct_loaded["failed"],
        "rejected_front_door": acct_loaded["rejected"],
        "storm_completed_ok": acct_storm["ok"],
        "storm_failed_structured": acct_storm["failed"],
        "faults_fired": plan.fired,
        "fired_by_site": plan.fired_by_site(),
        "storm": plan.describe(),
        "zero_lost": zero_lost,
        "compared_results": compared,
        "clean_results_bitwise": clean_bitwise,
        "same_seed_reproduces": same_seed,
        "wall_s_unloaded": round(unloaded["wall_s"], 3),
        "wall_s_loaded": round(loaded["wall_s"], 3),
        "wall_s_storm": round(stormed["wall_s"], 3),
    }


def gate_problems(row: dict) -> list[str]:
    """The acceptance checks ``main`` and bench_diff's tenancy gate share."""
    problems = []
    if row.get("error"):
        return [f"serve_tenancy: row errored: {row['error']}"]
    if row.get("zero_lost") is not True:
        problems.append("serve_tenancy: LOST REQUESTS — a submitted request "
                        "resolved as neither result, structured failure, "
                        "nor deterministic front-door rejection")
    if row.get("latency_bounded") is not True:
        problems.append(
            f"serve_tenancy: latency-class p99 under the bulk burst is "
            f"{row.get('latency_inflation')}x the unloaded p99 — exceeds "
            f"the {LATENCY_P99_CEILING}x ceiling (tenant isolation broke)")
    if row.get("fairness_ok") is not True:
        problems.append(
            f"serve_tenancy: Jain fairness {row.get('jain_fairness')} over "
            f"delivered bulk work is under the {JAIN_FLOOR} floor — the "
            f"burst tenant starved the drip tenant")
    if not row.get("brownout_transitions", 0):
        problems.append("serve_tenancy: the flood never climbed the "
                        "brownout ladder — the row proves nothing about "
                        "overload control")
    if row.get("brownout_signature_reproduced") is not True:
        problems.append("serve_tenancy: the same seed did NOT reproduce "
                        "the brownout transition log")
    if row.get("same_seed_reproduces") is not True:
        problems.append("serve_tenancy: the same seed did NOT reproduce "
                        "the same fault sequence")
    if row.get("clean_results_bitwise") is not True:
        problems.append("serve_tenancy: a multiply that succeeded under "
                        "the storm is NOT bitwise identical to the clean "
                        "loaded run")
    return problems


def run(quick: bool = True, seed: int = 0) -> list[dict]:
    """The ``tenancy`` benchmark section (wired into benchmarks.run)."""
    if quick:
        return [tenancy_mix(L=2, n_latency=8, n_burst=24, quota_burst=20,
                            n_drip=6, max_queue_depth=48, seed=seed)]
    return [tenancy_mix(L=2, n_latency=12, n_burst=36, quota_burst=32,
                        n_drip=8, max_queue_depth=64, seed=seed)]


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
