"""Multi-tenant SLO control plane (ISSUE 10): quotas, deficit-fair
scheduling, brownout ladder, warm-pool autoscaling, the latency lane.

The pure-policy tests (TokenBucket, DeficitFairScheduler, BrownoutLadder,
WarmPoolAutoscaler) run in microseconds with no device.  The service-level
tests carry the ``tenancy`` marker and compile one or two tiny L=2 programs
each — ``scripts/smoke.sh`` runs the quota/brownout spot-check before the
tiers.
"""
import math
import time

import pytest

import jax
import jax.numpy as jnp

from repro.serve.su3 import (
    AutoscaleConfig,
    BatcherConfig,
    BrownoutConfig,
    BrownoutLadder,
    DeadlineExceededError,
    DeficitFairScheduler,
    LoadShedError,
    ServeRequest,
    ServiceConfig,
    SLOPolicy,
    SU3Service,
    TenantQuota,
    TokenBucket,
    WarmPoolAutoscaler,
)
from repro.serve.su3.tenancy import SLO_BULK, SLO_LATENCY

S2 = 16  # L=2 sites


def _rand_ab(seed, n_sites=S2):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n_sites, 4, 3, 3, 2))
    a = jax.lax.complex(g[..., 0], g[..., 1])
    h = jax.random.normal(jax.random.PRNGKey(seed + 10_000), (4, 3, 3, 2))
    return a, jax.lax.complex(h[..., 0], h[..., 1])


def _rand_rhs(seed, n_sites=S2):
    g = jax.random.normal(jax.random.PRNGKey(seed + 77), (n_sites, 3, 2))
    return jax.lax.complex(g[..., 0], g[..., 1])


def _svc(**kw):
    cfg = dict(autotune=False, tile=16)
    cfg.update(kw)
    return SU3Service(ServiceConfig(**cfg))


# -- TokenBucket (pure) --------------------------------------------------------


def test_token_bucket_pure_burst_is_deterministic():
    # rate_per_s=0 never refills: the bucket is a fixed burst budget no
    # matter how much (fake) time passes — what the reproducible benches use
    b = TokenBucket(TenantQuota(rate_per_s=0.0, burst=3))
    assert [b.try_take(t) for t in (0.0, 10.0, 20.0, 99.0)] == \
        [True, True, True, False]
    assert b.try_take(1e9) is False


def test_token_bucket_refills_on_the_callers_clock():
    b = TokenBucket(TenantQuota(rate_per_s=2.0, burst=2))
    assert b.try_take(0.0) and b.try_take(0.0)
    assert not b.try_take(0.0)  # dry
    assert not b.try_take(0.25)  # 0.5 tokens < 1
    assert b.try_take(0.75)  # +1.0 more by now
    # refill caps at burst: a long idle gap does not bank extra credit
    assert b.try_take(100.0) and b.try_take(100.0)
    assert not b.try_take(100.0)


def test_tenant_quota_validates():
    with pytest.raises(ValueError):
        TenantQuota(rate_per_s=-1.0)
    with pytest.raises(ValueError):
        TenantQuota(burst=0)


# -- DeficitFairScheduler (pure) -----------------------------------------------


def test_drr_alternates_equal_weights():
    sched = DeficitFairScheduler()
    groups = [("a", SLO_BULK), ("b", SLO_BULK)]
    served = [sched.next_group(groups) for _ in range(6)]
    assert served.count(groups[0]) == 3
    assert served.count(groups[1]) == 3
    assert served[0] != served[1]  # no back-to-back monopoly at weight 1


def test_drr_weight_proportionality():
    pol = SLOPolicy()  # latency_weight=4, bulk_weight=1
    sched = DeficitFairScheduler(weight_for=pol.weight_for)
    lat, bulk = ("t", SLO_LATENCY), ("t", SLO_BULK)
    served = [sched.next_group([lat, bulk]) for _ in range(50)]
    assert served.count(lat) == 40
    assert served.count(bulk) == 10


def test_drr_non_starvation_bound():
    # the documented bound: a weight-w group banks a turn within
    # ceil(1/(q*w)) ring visits, and every other group holds the floor at
    # most ceil(1 + q*weight_h) consecutive turns between visits — so even
    # against an adversarial heavy group the light one is served within
    # ceil(1/(q*w)) * sum_h ceil(1 + q*weight_h) calls
    weights = {("heavy", SLO_BULK): 8.0, ("light", SLO_BULK): 0.25}
    sched = DeficitFairScheduler(weight_for=lambda g: weights[g])
    ring = list(weights)
    bound = math.ceil(1.0 / 0.25) * sum(
        math.ceil(1.0 + w) for g, w in weights.items() if g[0] != "light")
    gap = 0
    worst = 0
    for _ in range(400):
        g = sched.next_group(ring)
        if g == ("light", SLO_BULK):
            worst = max(worst, gap)
            gap = 0
        else:
            gap += 1
    assert 0 < worst <= bound
    assert sched.turns[("light", SLO_BULK)] >= 400 // (bound + 4)


def test_drr_idle_group_forfeits_banked_credit():
    sched = DeficitFairScheduler()
    a, b = ("a", SLO_BULK), ("b", SLO_BULK)
    for _ in range(10):
        assert sched.next_group([a]) == a  # b idle throughout
    # b returns: it gets fair alternation, not a banked-burst monopoly
    served = [sched.next_group([a, b]) for _ in range(4)]
    assert served.count(b) == 2


def test_drr_idle_returns_none_and_recovers():
    sched = DeficitFairScheduler()
    a = ("a", SLO_BULK)
    assert sched.next_group([]) is None
    assert sched.next_group([a]) == a


# -- BrownoutLadder (pure) -----------------------------------------------------

_BCFG = BrownoutConfig(enter_pressure=0.8, exit_pressure=0.3,
                       sustain_turns=2, exit_turns=3)


def test_brownout_escalates_only_on_sustained_pressure():
    lad = BrownoutLadder(_BCFG)
    assert lad.observe(0.9) is None  # one hot sample is not a brownout
    assert lad.observe(0.9) == 1
    assert lad.rung == 1
    assert lad.observe(0.9) is None  # streak reset on transition
    assert lad.observe(0.9) == 2
    lad.observe(0.9)
    assert lad.observe(0.9) == 3
    assert [lad.observe(0.9) for _ in range(4)] == [None] * 4  # capped


def test_brownout_dead_band_and_exit_hysteresis():
    lad = BrownoutLadder(_BCFG)
    lad.observe(0.9)
    lad.observe(0.9)
    assert lad.rung == 1
    # dead band (0.3 < p < 0.8): neither streak advances
    for _ in range(10):
        assert lad.observe(0.5) is None
    assert lad.rung == 1
    # calm exits only after exit_turns consecutive calm samples
    assert lad.observe(0.1) is None
    assert lad.observe(0.1) is None
    assert lad.observe(0.1) == 0
    assert lad.rung == 0


def test_brownout_signature_is_replay_deterministic():
    trace = [0.9, 0.9, 0.5, 0.9, 0.9, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1]
    a, b = BrownoutLadder(_BCFG), BrownoutLadder(_BCFG)
    for p in trace:
        a.observe(p)
    for p in trace:
        b.observe(p)
    assert a.signature() == b.signature()
    assert a.signature()  # the trace does transition
    # turn indices (not wall clock) key the log
    assert all(isinstance(t, int) for t, _f, _to in a.signature())


# -- WarmPoolAutoscaler (pure) -------------------------------------------------

_ACFG = AutoscaleConfig(enabled=True, min_hosts=1, grow_queue_depth=4,
                        grow_occupancy=0.9, shrink_queue_depth=1,
                        shrink_occupancy=0.25, grow_turns=2, shrink_turns=3)


def test_autoscaler_grows_after_sustained_heat_and_respects_max():
    sc = WarmPoolAutoscaler(_ACFG, max_hosts=2)
    assert sc.observe(depth_per_host=8, occupancy=0.5, active=1) == 0
    assert sc.observe(depth_per_host=8, occupancy=0.5, active=1) == 1
    # at max_hosts the controller holds no matter how hot
    assert sc.observe(depth_per_host=8, occupancy=1.0, active=2) == 0
    assert sc.observe(depth_per_host=8, occupancy=1.0, active=2) == 0


def test_autoscaler_shrinks_after_sustained_cold_and_respects_min():
    sc = WarmPoolAutoscaler(_ACFG, max_hosts=3)
    for _ in range(2):
        assert sc.observe(depth_per_host=0, occupancy=0.0, active=2) == 0
    assert sc.observe(depth_per_host=0, occupancy=0.0, active=2) == -1
    for _ in range(6):
        assert sc.observe(depth_per_host=0, occupancy=0.0, active=1) == 0


def test_autoscaler_streak_resets_on_signal_flip():
    sc = WarmPoolAutoscaler(_ACFG, max_hosts=2)
    sc.observe(depth_per_host=8, occupancy=0.5, active=1)
    sc.observe(depth_per_host=0, occupancy=0.0, active=1)  # flip resets hot
    assert sc.observe(depth_per_host=8, occupancy=0.5, active=1) == 0
    assert sc.observe(depth_per_host=8, occupancy=0.5, active=1) == 1


def test_autoscale_config_validates_hysteresis():
    with pytest.raises(ValueError):
        AutoscaleConfig(grow_queue_depth=1, shrink_queue_depth=1)
    with pytest.raises(ValueError):
        AutoscaleConfig(grow_occupancy=0.2, shrink_occupancy=0.3)


# -- service-level: quotas, classes, brownout, the latency lane ----------------


@pytest.mark.tenancy
def test_default_tenant_keeps_legacy_metrics_shape():
    svc = _svc()
    a, b = _rand_ab(0)
    rid = svc.submit(a, b, k=1)
    svc.run_until_drained()
    assert not isinstance(svc.pop_result(rid), Exception)
    snap = svc.metrics.snapshot()
    assert snap["admitted"] == 1 and snap["completed"] == 1
    assert snap["admitted_by_class"] == {"default/bulk": 1}
    assert list(snap["latency_by_class_ms"]) == ["default/bulk"]
    assert snap["brownout_rung"] == 0 and snap["quota_rejected"] == 0


@pytest.mark.tenancy
def test_quota_burst_rejects_at_the_front_door():
    svc = _svc(quotas={"metered": TenantQuota(rate_per_s=0.0, burst=2)})
    a, b = _rand_ab(1)
    ids = [svc.submit(a, b, k=1, tenant="metered") for _ in range(4)]
    assert ids[0] is not None and ids[1] is not None
    assert ids[2] is None and ids[3] is None  # bucket dry: rejected pre-queue
    # unmetered tenants never hit the bucket
    assert svc.submit(a, b, k=1, tenant="other") is not None
    snap = svc.metrics.snapshot()
    assert snap["quota_rejected"] == 2
    assert snap["quota_rejected_by_tenant"] == {"metered": 2}
    assert svc.queued() == 3
    svc.run_until_drained()


@pytest.mark.tenancy
def test_per_tenant_per_class_splits_sum_to_legacy_totals():
    svc = _svc()
    a, b = _rand_ab(2)
    svc.submit(a, b, k=1, tenant="t1")  # default bulk
    svc.submit(a, b, k=1, tenant="t2", slo="latency")
    u, _ = _rand_ab(3)
    svc.submit_stencil(u, _rand_rhs(3), tenant="t1")  # default latency
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    assert snap["completed"] == 3
    assert snap["admitted_by_class"] == {
        "t1/bulk": 1, "t2/latency": 1, "t1/latency": 1}
    assert sum(v["count"] for v in snap["latency_by_class_ms"].values()) == 3


@pytest.mark.tenancy
def test_shed_attributes_the_beneficiary_kind():
    svc = _svc(batcher=BatcherConfig(max_queue_depth=1))
    a, b = _rand_ab(4)
    rid_bulk = svc.submit(a, b, k=1)
    rid_solve = svc.submit_solve(a, _rand_rhs(4), tol=1e-3, max_iters=8)
    assert rid_bulk is not None and rid_solve is not None
    out = svc.pop_result(rid_bulk)
    assert isinstance(out, LoadShedError)
    assert out.shed_for_kind == "solve"
    snap = svc.metrics.snapshot()
    assert snap["shed_for_kind"] == {"solve": 1}  # the beneficiary, fixed
    assert snap["shed_by_kind"] == {"multiply": 1}  # the victim, unchanged
    assert snap["shed_by_class"] == {"default/bulk": 1}
    svc.run_until_drained()


@pytest.mark.tenancy
def test_latency_lane_is_never_shed():
    svc = _svc(batcher=BatcherConfig(max_queue_depth=1))
    a, b = _rand_ab(5)
    rid_lat = svc.submit(a, b, k=1, slo="latency")
    # a solve outranks multiplies by PRIORITY, but the seated request is
    # latency-class: nothing sheddable, so the solve is rejected instead
    rid_solve = svc.submit_solve(a, _rand_rhs(5), tol=1e-3, max_iters=8)
    assert rid_lat is not None and rid_solve is None
    assert svc.metrics.snapshot()["shed"] == 0
    svc.run_until_drained()
    assert not isinstance(svc.pop_result(rid_lat), Exception)


@pytest.mark.tenancy
def test_brownout_rung3_rejects_bulk_with_retry_after_hint():
    svc = _svc(brownout=BrownoutConfig(retry_after_s=0.25))
    svc._brownout.rung = 3  # pin the ladder at full brownout
    a, b = _rand_ab(6)
    rid = svc.submit(a, b, k=1)  # bulk: rejected at the door
    assert rid is not None  # zero-lost: the id resolves to a structured shed
    out = svc.pop_result(rid)
    assert isinstance(out, LoadShedError)
    assert out.shed_for_kind == "brownout"
    assert out.retry_after_s == pytest.approx(0.25)
    assert "retry after" in str(out)
    # the latency lane is never browned out
    rid_lat = svc.submit(a, b, k=1, slo="latency")
    assert rid_lat is not None and not svc.has_result(rid_lat)
    svc.run_until_drained()
    assert not isinstance(svc.pop_result(rid_lat), Exception)
    assert svc.metrics.snapshot()["shed_for_kind"] == {"brownout": 1}


@pytest.mark.tenancy
def test_brownout_rung1_caps_bulk_queue_share():
    svc = _svc(batcher=BatcherConfig(max_queue_depth=4),
               brownout=BrownoutConfig(bulk_queue_fraction=0.5))
    svc._brownout.rung = 1
    a, b = _rand_ab(7)
    ids = [svc.submit(a, b, k=1) for _ in range(3)]
    # bulk keeps floor(4 * 0.5) = 2 queue slots; the third arrival sheds
    assert svc.queued() == 2
    assert isinstance(svc.pop_result(ids[2]), LoadShedError)
    svc.run_until_drained()


@pytest.mark.tenancy
def test_brownout_rung2_degrades_bulk_solves():
    svc = _svc(brownout=BrownoutConfig(degrade_solve_factor=4),
               solve_iters_per_step=8)
    svc._brownout.rung = 2
    a, _ = _rand_ab(8)
    rid = svc.submit_solve(a, _rand_rhs(8), tol=1e-5, max_iters=64,
                           slo="bulk")
    svc.run_until_drained()
    assert not isinstance(svc.pop_result(rid), Exception)
    snap = svc.metrics.snapshot()
    assert snap["brownout_degraded_solve_turns"] >= 1
    # 8 iters/turn degraded to 2: more scheduling turns than the undegraded
    # solve would have used
    assert snap["kind_iterations"]["solve"] >= 2


@pytest.mark.tenancy
def test_latency_lane_under_bulk_backlog():
    # a latency multiply arriving behind a bulk backlog is served within the
    # deficit scheduler's stated bound — ceil(1/(q*w)) * sum over the other
    # groups of ceil(1 + q*w_h) turns — and no bulk request is lost
    svc = _svc(batcher=BatcherConfig(max_batch=1, warm_batch_sizes=(1,)))
    a, b = _rand_ab(9)
    bulk_ids = [svc.submit(a, b, k=1) for _ in range(6)]
    svc.step()  # the backlog owns the first turn
    lat_id = svc.submit(a, b, k=1, slo="latency")
    q, weight = svc._sched.quantum, svc.cfg.slo.weight_for
    lat, bulk = ("default", SLO_LATENCY), ("default", SLO_BULK)
    bound = math.ceil(1.0 / (q * weight(lat))) * math.ceil(1.0 + q * weight(bulk))
    turns = 0
    while not svc.has_result(lat_id):
        svc.step()
        turns += 1
        assert turns <= bound, f"latency lane waited {turns} > {bound} turns"
    assert not isinstance(svc.pop_result(lat_id), Exception)
    svc.run_until_drained()
    for rid in bulk_ids:  # zero lost
        assert not isinstance(svc.pop_result(rid), Exception)
    snap = svc.metrics.snapshot()
    assert snap["completed"] == 7 and snap["shed"] == 0


@pytest.mark.tenancy
def test_autoscale_grows_under_backlog_and_shrinks_when_idle():
    svc = _svc(
        hosts=2,
        autoscale=AutoscaleConfig(
            enabled=True, min_hosts=1, grow_queue_depth=2,
            shrink_queue_depth=1, shrink_occupancy=0.25,
            grow_turns=1, shrink_turns=2),
    )
    assert svc._active_hosts == 1
    a, b = _rand_ab(10)
    ids = [svc.submit(a, b, k=1) for _ in range(4)]
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    assert snap["scale_ups"] >= 1  # backlog grew the pool
    for rid in ids:
        assert not isinstance(svc.pop_result(rid), Exception)
    for _ in range(8):  # idle: cold streak retires the extra host
        svc.step()
    snap = svc.metrics.snapshot()
    assert snap["scale_downs"] >= 1
    assert snap["active_hosts"] == 1


@pytest.mark.tenancy
def test_scale_down_vetoed_by_seated_latency_request():
    svc = _svc(hosts=2,
               autoscale=AutoscaleConfig(enabled=True, min_hosts=1))
    svc._active_hosts = 2
    seated = ServeRequest(req_id=7, a=None, b=None, L=2, k=1,
                          arrival_s=0.0, kind="solve", slo="latency")
    svc._solves[1] = {"req": seated}  # host 1 holds a seated latency solve
    svc._scale_down()
    assert svc._active_hosts == 2  # vetoed
    assert svc.metrics.snapshot()["scale_downs"] == 0
    del svc._solves[1]
    svc._scale_down()
    assert svc._active_hosts == 1


@pytest.mark.tenancy
def test_deficit_fair_turns_across_tenants_in_service():
    # two backlogged bulk tenants on one host split dispatch turns fairly
    svc = _svc(batcher=BatcherConfig(max_batch=1, max_queue_depth=64))
    a, b = _rand_ab(11)
    ids = []
    for i in range(4):
        ids.append(svc.submit(a, b, k=1, tenant="t1"))
        ids.append(svc.submit(a, b, k=1, tenant="t2"))
    svc.run_until_drained()
    for rid in ids:
        assert not isinstance(svc.pop_result(rid), Exception)
    turns = svc._sched.turns
    assert turns[("t1", "bulk")] == turns[("t2", "bulk")] == 4


@pytest.mark.tenancy
def test_slo_class_deadline_default_applies():
    svc = _svc(slo=SLOPolicy(bulk_deadline_s=0.001))
    a, b = _rand_ab(12)
    rid = svc.submit(a, b, k=1)  # bulk: inherits the 1 ms class deadline
    time.sleep(0.01)
    svc.step()
    out = svc.pop_result(rid)
    assert isinstance(out, DeadlineExceededError)
    # latency class has no default here: same traffic survives
    rid2 = svc.submit(a, b, k=1, slo="latency")
    time.sleep(0.01)
    svc.run_until_drained()
    assert not isinstance(svc.pop_result(rid2), Exception)
