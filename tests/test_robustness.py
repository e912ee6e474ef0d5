"""Request-lifecycle robustness: deadlines, retries, shedding, quarantine,
fault storms (ISSUE 9).

Pure-logic tests (RetryPolicy, HostHealth, ServiceMetrics counters) run in
microseconds; the service-level tests compile one or two tiny L=2 programs
each.  Fault-injection tests carry the ``chaos`` marker —
``scripts/smoke.sh`` runs :func:`test_storm_zero_lost_and_bitwise_clean`
as its chaos spot-check before the tiers.
"""
import asyncio
import random
import time

import pytest

import jax
import jax.numpy as jnp

from repro.chaos import FaultPlan, FaultSpec, storm
from repro.core.su3.plan import CGDivergedError
from repro.serve.su3 import (
    PRIORITY,
    BatcherConfig,
    DeadlineExceededError,
    HostHealth,
    LoadShedError,
    RequestFailure,
    RetriesExhaustedError,
    RetryPolicy,
    ServeRequest,
    ServiceConfig,
    ServiceMetrics,
    SU3Service,
)
from repro.serve.su3.batcher import DynamicBatcher

S2 = 16  # L=2 sites


def _rand_ab(seed, n_sites=S2):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n_sites, 4, 3, 3, 2))
    a = jax.lax.complex(g[..., 0], g[..., 1])
    h = jax.random.normal(jax.random.PRNGKey(seed + 10_000), (4, 3, 3, 2))
    return a, jax.lax.complex(h[..., 0], h[..., 1])


def _svc(**kw):
    cfg = dict(autotune=False, tile=16)
    cfg.update(kw)
    return SU3Service(ServiceConfig(**cfg))


def _req(i, L=2, k=1, priority=0, deadline_s=0.0, arrival=None):
    return ServeRequest(req_id=i, a=None, b=None, L=L, k=k,
                        arrival_s=i + 1.0 if arrival is None else arrival,
                        priority=priority, deadline_s=deadline_s)


# -- RetryPolicy (pure) --------------------------------------------------------


def test_retry_policy_backoff_doubles_to_cap_with_bounded_jitter():
    pol = RetryPolicy(base_s=0.01, cap_s=0.05, jitter=0.25)
    rng = random.Random(0)
    raws = [0.01, 0.02, 0.04, 0.05, 0.05]  # doubles, then pinned at cap
    for attempt, raw in enumerate(raws, start=1):
        for _ in range(20):
            d = pol.backoff_s(attempt, rng)
            assert raw <= d <= raw * 1.25


def test_retry_policy_zero_jitter_is_deterministic():
    pol = RetryPolicy(base_s=0.002, cap_s=0.25, jitter=0.0)
    rng = random.Random(3)
    assert pol.backoff_s(1, rng) == 0.002
    assert pol.backoff_s(4, rng) == 0.016
    assert pol.backoff_s(40, rng) == 0.25


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="max_retries"):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError, match="base_s"):
        RetryPolicy(base_s=0.5, cap_s=0.1)
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=2.0)
    with pytest.raises(ValueError, match="budget"):
        RetryPolicy(budget=-5)


# -- HostHealth (pure) ---------------------------------------------------------


def test_host_health_quarantines_after_consecutive_failures():
    h = HostHealth(3, quarantine_after=2)
    assert h.record_failure(0, "boom") is False
    assert h.record_failure(0, "boom") is True  # the crossing returns True
    assert h.record_failure(0, "boom") is False  # already latched: once only
    assert h.quarantined() == {0} and h.is_quarantined(0)
    assert h.healthy_hosts() == [1, 2]
    snap = h.snapshot()
    assert snap["quarantined"] == [0] and snap["last_cause"][0] == "boom"


def test_host_health_success_resets_the_consecutive_count():
    h = HostHealth(2, quarantine_after=3)
    h.record_failure(1, "a")
    h.record_failure(1, "b")
    h.record_success(1)
    assert h.consecutive[1] == 0
    assert h.record_failure(1, "c") is False  # count restarted, no latch
    assert h.failures[1] == 3 and h.successes[1] == 1


def test_host_health_never_quarantines_the_last_healthy_host():
    solo = HostHealth(1, quarantine_after=1)
    for _ in range(5):
        assert solo.record_failure(0, "x") is False  # keeps retrying instead
    assert solo.quarantined() == set()

    pair = HostHealth(2, quarantine_after=1)
    assert pair.record_failure(0, "x") is True
    assert pair.record_failure(1, "x") is False  # 1 is the last one standing
    assert pair.healthy_hosts() == [1]


def test_host_health_reinstate_clears_the_latch():
    h = HostHealth(2, quarantine_after=1)
    h.record_failure(0, "x")
    h.reinstate(0)
    assert h.healthy_hosts() == [0, 1] and h.consecutive[0] == 0
    with pytest.raises(ValueError):
        HostHealth(0)
    with pytest.raises(ValueError):
        HostHealth(2, quarantine_after=0)


# -- ServiceMetrics robustness counters (pure) ---------------------------------


def test_metrics_robustness_counters_and_per_kind_splits():
    m = ServiceMetrics()
    m.record_reject("solve")
    m.record_reject("solve")
    m.record_reject()  # defaults to multiply: pre-existing call sites
    m.record_shed("multiply")
    m.record_timeout("solve")
    m.record_retry()
    m.record_retry(2)
    m.record_retries_exhausted()
    m.record_fault()
    m.record_quarantine(reseated=3)
    snap = m.snapshot()
    assert snap["rejected"] == 3  # the pre-existing total key is unchanged
    assert snap["rejected_by_kind"] == {"solve": 2, "multiply": 1}
    assert snap["shed"] == 1 and snap["shed_by_kind"] == {"multiply": 1}
    assert snap["timeouts"] == 1 and snap["timeouts_by_kind"] == {"solve": 1}
    assert snap["retries"] == 3
    assert snap["retries_exhausted"] == 1
    assert snap["faults_injected"] == 1
    assert snap["quarantines"] == 1 and snap["reseated"] == 3
    # the legacy surface bench rows key on is still there
    for key in ("completed", "dispatches", "latency_p50_ms",
                "mean_batch_occupancy", "queue_depth_max"):
        assert key in snap


# -- batcher eviction/shedding (pure queue ops) --------------------------------


def test_batcher_evict_expired_removes_only_past_deadline():
    b = DynamicBatcher(BatcherConfig(max_batch=8, warm_batch_sizes=(1, 8)))
    b.submit(_req(0, deadline_s=5.0))
    b.submit(_req(1, deadline_s=100.0))
    b.submit(_req(2))  # no deadline: never expires
    evicted = b.evict_expired(now=10.0)
    assert [r.req_id for r in evicted] == [0]
    assert len(b) == 2


def test_batcher_sheds_youngest_lowest_priority_first():
    b = DynamicBatcher(BatcherConfig(max_batch=8, warm_batch_sizes=(1, 8)))
    b.submit(_req(0, priority=PRIORITY["multiply"], arrival=1.0))
    b.submit(_req(1, priority=PRIORITY["multiply"], arrival=2.0))
    b.submit(_req(2, priority=PRIORITY["solve"], arrival=3.0))
    victim = b.shed_lowest(max_priority=PRIORITY["solve"])
    assert victim.req_id == 1  # youngest of the lowest priority class
    # nothing queued sits below multiply priority, so a multiply arrival
    # finds no victim, and a queue of solves never sheds for another solve
    assert b.shed_lowest(max_priority=PRIORITY["multiply"]) is None
    b2 = DynamicBatcher(BatcherConfig(max_batch=8, warm_batch_sizes=(1, 8)))
    b2.submit(_req(0, priority=PRIORITY["solve"]))
    assert b2.shed_lowest(max_priority=PRIORITY["solve"]) is None


# -- deadlines (service) -------------------------------------------------------


def test_deadline_evicts_queued_request_with_structured_timeout():
    svc = _svc()
    a, b = _rand_ab(0)
    rid = svc.submit(a, b, k=1, deadline_s=0.01)
    time.sleep(0.05)
    svc.step()  # the sweep runs before dispatch
    out = svc.pop_result(rid)
    assert isinstance(out, DeadlineExceededError)
    assert out.req_id == rid and out.kind == "multiply"
    assert out.waited_s >= 0.01 and out.partial is None
    assert svc.metrics.snapshot()["timeouts_by_kind"] == {"multiply": 1}
    assert not svc.pending()


def test_deadline_evicts_active_solve_and_carries_partial():
    from benchmarks.cg_solve import _problem

    svc = _svc(solve_iters_per_step=1)
    u, b = _problem(2)
    rid = svc.submit_solve(u, b, tol=1e-12, max_iters=500, deadline_s=30.0)
    svc.step()  # seat + first iterations
    assert svc._solves  # seated
    active = next(iter(svc._solves.values()))
    active["req"].deadline_s = time.perf_counter() - 1.0  # force expiry
    svc.step()
    out = svc.pop_result(rid)
    assert isinstance(out, DeadlineExceededError) and out.kind == "solve"
    assert out.partial is not None  # the best iterate rides out
    assert out.partial.shape[0] == 2**4
    assert not svc._solves and not svc.pending()


@pytest.mark.chaos
def test_deadline_eviction_on_quarantined_host_reseats_then_times_out():
    # satellite edge case: work queued on a host that gets quarantined is
    # re-seated onto a healthy host's queue; an expired deadline must still
    # produce a structured timeout (never a silent drop) after the move
    svc = _svc(hosts=2, quarantine_after=1,
               batcher=BatcherConfig(max_batch=1, warm_batch_sizes=(1,),
                                     max_queue_depth=8))
    a, b = _rand_ab(5)
    home = svc.router.host_for(2)
    rid = svc.submit(a, b, k=8, deadline_s=60.0)
    assert len(svc._batchers[home]) == 1  # queued on the home host
    svc.health.record_failure(home, "test latch")
    svc._quarantine(home)
    assert svc.health.is_quarantined(home)
    snap = svc.metrics.snapshot()
    assert snap["quarantines"] == 1 and snap["reseated"] == 1
    (healthy,) = [h for h in range(2) if h != home]
    assert len(svc._batchers[home]) == 0
    moved = [r for q in svc._batchers[healthy]._buckets.values() for r in q]
    assert [r.req_id for r in moved] == [rid], "request lost during re-seat"
    moved[0].deadline_s = time.perf_counter() - 1.0
    svc.run_until_drained()
    out = svc.pop_result(rid)
    assert isinstance(out, DeadlineExceededError)
    assert not svc.pending()
    assert svc.metrics.snapshot()["dispatches"] == 0


def test_reseat_resolves_already_expired_deadline_exactly_once():
    # the deadline-expiry x re-seat race: a request whose deadline passed
    # BEFORE the quarantine/scale-down re-seat runs must resolve as exactly
    # one DeadlineExceededError at re-seat time — never resubmitted for the
    # next sweep to evict (double resolution), never silently dropped
    svc = _svc(hosts=2)
    now = time.perf_counter()
    req = _req(1, deadline_s=now - 0.01, arrival=now - 0.5)
    reseated = svc._reseat([req], "re-seat rejected")
    assert reseated == 0
    assert svc.queued() == 0  # never re-entered any queue
    out = svc.pop_result(1)
    assert isinstance(out, DeadlineExceededError)
    assert not svc.has_result(1)  # resolved once; nothing left behind
    assert svc.metrics.snapshot()["timeouts_by_kind"] == {"multiply": 1}
    # a live-deadline companion in the same batch re-seats normally
    fresh = _req(2, deadline_s=now + 60.0, arrival=now)
    assert svc._reseat([fresh], "re-seat rejected") == 1
    assert svc.queued() == 1
    assert svc.metrics.snapshot()["timeouts"] == 1


# -- load shedding -------------------------------------------------------------


def test_solve_arrival_sheds_queued_multiply_under_backpressure():
    from benchmarks.cg_solve import _problem

    svc = _svc(batcher=BatcherConfig(max_batch=1, warm_batch_sizes=(1,),
                                     max_queue_depth=1))
    a, b = _rand_ab(6)
    rid_m = svc.submit(a, b, k=1)  # fills the depth-1 queue
    u, rhs = _problem(2)
    rid_s = svc.submit_solve(u, rhs, tol=1e-6, max_iters=64)
    assert rid_s is not None  # admitted by shedding the multiply
    out = svc.pop_result(rid_m)
    assert isinstance(out, LoadShedError)
    assert out.shed_for_kind == "solve" and out.priority == PRIORITY["multiply"]
    svc.run_until_drained()
    x = svc.pop_result(rid_s)
    assert not isinstance(x, Exception) and bool(jnp.all(jnp.isfinite(jnp.real(x))))
    snap = svc.metrics.snapshot()
    assert snap["shed"] == 1 and snap["shed_by_kind"] == {"multiply": 1}


def test_multiply_arrival_cannot_shed_an_equal_priority_multiply():
    svc = _svc(batcher=BatcherConfig(max_batch=1, warm_batch_sizes=(1,),
                                     max_queue_depth=1))
    a, b = _rand_ab(7)
    rid1 = svc.submit(a, b, k=1)
    rid2 = svc.submit(*_rand_ab(8), k=1)  # equal priority: rejected, not shed
    assert rid1 is not None and rid2 is None
    assert svc.metrics.snapshot()["rejected_by_kind"] == {"multiply": 1}
    svc.run_until_drained()
    assert not isinstance(svc.pop_result(rid1), Exception)


# -- arun backpressure backoff (satellite: no busy-spin) -----------------------


def test_arun_backs_off_exponentially_instead_of_busy_spinning():
    svc = _svc(retry=RetryPolicy(base_s=0.02, cap_s=0.2, jitter=0.2))
    a, b = _rand_ab(9)
    times = []
    real_submit, real_step = svc.submit, svc.step
    svc.step = lambda: 0  # the service is stalled while it rejects

    def stub(aa, bb, k=None, deadline_s=None, **kw):
        times.append(time.perf_counter())
        if len(times) <= 4:
            return None  # sustained backpressure
        svc.step = real_step  # service unstalls; let the request complete
        return real_submit(aa, bb, k, deadline_s=deadline_s, **kw)

    svc.submit = stub
    out = asyncio.run(svc.arun(a, b, k=1))
    assert bool(jnp.all(jnp.isfinite(jnp.real(out))))
    assert len(times) == 5  # 4 rejections + 1 success: no spin storm
    gaps = [t1 - t0 for t0, t1 in zip(times, times[1:])]
    # gap 0 is the same-tick fast path; the rest follow the jittered
    # exponential schedule (>= 90% of the raw delay, well past spin speed)
    assert gaps[1] >= 0.02 * 0.9
    assert gaps[2] >= 0.04 * 0.9
    assert gaps[3] >= 0.08 * 0.9


def test_arun_raises_structured_failures():
    svc = _svc()
    a, b = _rand_ab(10)
    real_step = svc.step

    def slow_step():  # the deadline lapses before the first dispatch runs
        time.sleep(0.02)
        return real_step()

    svc.step = slow_step

    async def go():
        with pytest.raises(DeadlineExceededError):
            await svc.arun(a, b, k=1, deadline_s=0.01)

    asyncio.run(go())


# -- fault storms (chaos) ------------------------------------------------------


def _storm_svc(plan, **kw):
    cfg = dict(
        autotune=False, tile=16, faults=plan,
        retry=RetryPolicy(max_retries=6, base_s=1e-6, cap_s=1e-5),
        batcher=BatcherConfig(max_batch=4, warm_batch_sizes=(1, 2, 4),
                              max_queue_depth=64),
    )
    cfg.update(kw)
    return SU3Service(ServiceConfig(**cfg))


@pytest.mark.chaos
def test_storm_zero_lost_and_bitwise_clean():
    """The smoke.sh chaos spot-check: a seeded dispatch+kernel+pool storm
    over a multiply stream loses nothing, and every retried success is
    bitwise identical to the fault-free baseline."""
    reqs = [_rand_ab(100 + i) for i in range(6)]

    def run_once(plan):
        svc = _storm_svc(plan)
        ids = [svc.submit(a, b, k=2) for a, b in reqs]
        svc.run_until_drained()
        return {rid: svc.pop_result(rid) for rid in ids}, svc

    clean, _ = run_once(None)
    assert all(not isinstance(v, Exception) for v in clean.values())

    plan = storm(13, dispatch_p=0.5, kernel_p=0.4, pool_p=0.5, max_fires=4)
    chaotic, svc = run_once(plan)
    assert plan.fired > 0, "the storm must actually fire"
    for rid_c, rid_b in zip(chaotic, clean):
        out = chaotic[rid_c]
        assert out is not None, "lost request"
        if isinstance(out, Exception):
            assert isinstance(out, RequestFailure)  # structured, attributable
        else:
            assert bool(jnp.array_equal(out, clean[rid_b]))
    assert svc.metrics.snapshot()["faults_injected"] >= plan.fired - 1
    # same seed, same schedule -> same per-site fault sequence end-to-end
    replay_plan = plan.reset()
    run_once(replay_plan)
    key = lambda e: (e["site"], e["action"], e["site_seq"])  # noqa: E731
    assert sorted(map(key, plan.log())) == sorted(map(key, replay_plan.log()))


@pytest.mark.chaos
def test_unbounded_dispatch_failure_exhausts_retries_structurally():
    plan = FaultPlan(0, {"dispatch": FaultSpec(probability=1.0,
                                               actions=("fail",))})
    svc = _storm_svc(plan, retry=RetryPolicy(max_retries=2, base_s=1e-6,
                                             cap_s=1e-5))
    a, b = _rand_ab(11)
    rid = svc.submit(a, b, k=1)
    svc.run_until_drained()
    out = svc.pop_result(rid)
    assert isinstance(out, RetriesExhaustedError)
    assert out.attempts == 3  # first try + 2 retries
    assert "dispatch" in out.cause
    assert not svc.pending()  # drained, never hung
    assert svc.health.quarantined() == set()  # a lone host is never latched


@pytest.mark.chaos
def test_quarantine_reseats_onto_the_healthy_host_bitwise_clean():
    # host A fails 3 consecutive dispatches -> latched; its work re-homes to
    # host B and completes identical to a clean single-host run
    plan = FaultPlan(1, {"dispatch": FaultSpec(probability=1.0,
                                               actions=("fail",),
                                               max_fires=3)})
    svc = _storm_svc(plan, hosts=2, quarantine_after=3,
                     retry=RetryPolicy(max_retries=10, base_s=1e-6,
                                       cap_s=1e-5))
    a, b = _rand_ab(12)
    home = svc.router.host_for(2)
    rid = svc.submit(a, b, k=2)
    svc.run_until_drained(max_steps=100_000)
    out = svc.pop_result(rid)
    assert not isinstance(out, Exception)
    assert svc.health.quarantined() == {home}
    assert svc.metrics.snapshot()["quarantines"] == 1
    ref_svc = _svc()
    rid_ref = ref_svc.submit(a, b, k=2)
    ref_svc.run_until_drained()
    assert bool(jnp.array_equal(out, ref_svc.pop_result(rid_ref)))
    svc.health.reinstate(home)
    assert svc.health.healthy_hosts() == [0, 1]


_BATCH_FAULTS = {
    "dispatch": {"dispatch": FaultSpec(probability=1.0, actions=("fail",),
                                       max_fires=1)},
    "nonfinite": {"kernel": FaultSpec(probability=1.0, actions=("nan",),
                                      max_fires=1)},
}


@pytest.mark.chaos
@pytest.mark.parametrize("hosts", [1, 2])
@pytest.mark.parametrize("fault", sorted(_BATCH_FAULTS))
def test_batch_fault_retries_to_bitwise_clean(fault, hosts):
    # a failed launch or a poisoned (NaN) output is never delivered: the
    # coalesced batch re-runs through the retry path and finishes bitwise
    # equal to a clean run; with quarantine_after=1 on two hosts the retry
    # lands on the healthy host, and a lone host is never latched
    plan = FaultPlan(7, _BATCH_FAULTS[fault])
    svc = _storm_svc(plan, hosts=hosts, quarantine_after=1)
    reqs = [_rand_ab(400 + i) for i in range(2)]
    home = svc.router.host_for(2)
    ids = [svc.submit(a, b, k=2) for a, b in reqs]
    svc.run_until_drained()
    assert plan.fired == 1
    snap = svc.metrics.snapshot()
    assert snap["retries"] == 2 and snap["retries_exhausted"] == 0
    # the same batch dispatched clean on the runner that served the retry
    clean = svc.runner_for(2).multiply(
        jnp.stack([a for a, _ in reqs]), jnp.stack([b for _, b in reqs]), k=2)
    for i, rid in enumerate(ids):
        out = svc.pop_result(rid)
        assert not isinstance(out, Exception)
        assert bool(jnp.array_equal(out, clean[i]))
    if hosts == 2:
        (healthy,) = [h for h in range(2) if h != home]
        assert svc.health.quarantined() == {home}
        assert snap["quarantines"] == 1
        # the one delivered dispatch ran on the healthy host
        assert snap["host_dispatches"] == {str(healthy): 1}
    else:
        assert svc.health.quarantined() == set()


@pytest.mark.chaos
def test_solve_kernel_poison_retries_to_the_clean_answer():
    # one poisoned CG residual -> the numerics guard unseats the solve, the
    # retry re-runs it from scratch, and the answer matches the clean run
    from benchmarks.cg_solve import _problem

    u, b = _problem(2)
    clean_svc = _svc(solve_iters_per_step=4)
    rid0 = clean_svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    clean_svc.run_until_drained()
    x_clean = clean_svc.pop_result(rid0)

    plan = FaultPlan(4, {"kernel": FaultSpec(probability=1.0,
                                             actions=("nan",), max_fires=1)})
    svc = _storm_svc(plan, solve_iters_per_step=4)
    rid = svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    svc.run_until_drained()
    out = svc.pop_result(rid)
    assert plan.fired == 1
    assert svc.metrics.snapshot()["retries"] >= 1
    assert not isinstance(out, Exception)
    assert bool(jnp.array_equal(out, x_clean))


@pytest.mark.chaos
def test_solve_divergence_is_structured_not_a_hang():
    # an unbounded kernel-poison storm makes every retry diverge: the solve
    # must resolve as CGDivergedError with the fault provenance intact
    from benchmarks.cg_solve import _problem

    u, b = _problem(2)
    plan = FaultPlan(5, {"kernel": FaultSpec(probability=1.0,
                                             actions=("nan",))})
    svc = _storm_svc(plan, solve_iters_per_step=2,
                     retry=RetryPolicy(max_retries=1, base_s=1e-6,
                                       cap_s=1e-5))
    rid = svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    svc.run_until_drained()
    out = svc.pop_result(rid)
    assert isinstance(out, CGDivergedError)
    assert "non-finite" in str(out)
    assert not svc.pending()
