"""Serving subsystem: dynamic batcher, SU3Service, metrics, bf16 plans,
the host-sharded pool, and the multiply path's padding, mixed-L and
deadline contracts."""
import asyncio
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # smoke's fast tier skips these (-m "not slow")

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.su3.layouts import Layout
from repro.core.su3.plan import verify_tolerance
from repro.kernels import ref
from repro.serve.su3 import (
    BatcherConfig,
    DeadlineExceededError,
    DynamicBatcher,
    ServeRequest,
    ServiceConfig,
    ServiceMetrics,
    SU3Service,
)

S2 = 16  # L=2 lattice sites


def _rand_a(seed, n_sites=S2):
    a = jax.random.normal(jax.random.PRNGKey(seed), (n_sites, 4, 3, 3, 2))
    return jax.lax.complex(a[..., 0], a[..., 1])


def _rand_b(seed):
    b = jax.random.normal(jax.random.PRNGKey(seed), (4, 3, 3, 2))
    return jax.lax.complex(b[..., 0], b[..., 1])


def _req(i, L=2, k=1, arrival=0.0):
    return ServeRequest(req_id=i, a=None, b=None, L=L, k=k, arrival_s=arrival or i + 1.0)


def _svc(**kw):
    cfg = dict(autotune=False, tile=16)
    cfg.update(kw)
    return SU3Service(ServiceConfig(**cfg))


# -- batcher -------------------------------------------------------------------


def test_batcher_buckets_by_L_and_k():
    b = DynamicBatcher(BatcherConfig(max_batch=8, warm_batch_sizes=(1, 2, 4, 8)))
    for i, (L, k) in enumerate([(2, 1), (2, 2), (4, 1), (2, 1)]):
        assert b.submit(_req(i, L=L, k=k))
    assert len(b) == 4
    assert b.bucket_depths() == {(2, 1): 2, (2, 2): 1, (4, 1): 1}
    batch = b.next_batch()  # oldest head: req 0 in bucket (2, 1)
    assert batch.key == (2, 1) and [r.req_id for r in batch.requests] == [0, 3]
    assert len(b) == 2


def test_batcher_oldest_bucket_first_no_starvation():
    b = DynamicBatcher(BatcherConfig(max_batch=8, warm_batch_sizes=(1, 8)))
    b.submit(_req(0, L=4, k=1, arrival=1.0))
    b.submit(_req(1, L=2, k=1, arrival=2.0))
    b.submit(_req(2, L=4, k=1, arrival=3.0))
    assert b.next_batch().key == (4, 1)  # head req 0 is oldest
    assert b.next_batch().key == (2, 1)  # now req 1 is oldest


def test_batcher_pads_to_warm_size_and_reports_occupancy():
    cfg = BatcherConfig(max_batch=8, warm_batch_sizes=(1, 2, 4, 8))
    b = DynamicBatcher(cfg)
    for i in range(3):
        b.submit(_req(i))
    batch = b.next_batch()
    assert batch.padded_size == 4 and batch.pad == 1
    assert batch.occupancy == pytest.approx(0.75)
    assert cfg.padded_size(9) == 9  # past the largest warm size: exact


def test_batcher_max_batch_caps_coalescing():
    b = DynamicBatcher(BatcherConfig(max_batch=2, warm_batch_sizes=(1, 2)))
    for i in range(5):
        b.submit(_req(i))
    sizes = []
    while (batch := b.next_batch()) is not None:
        sizes.append(len(batch.requests))
    assert sizes == [2, 2, 1]


def test_batcher_backpressure():
    b = DynamicBatcher(BatcherConfig(max_queue_depth=2))
    assert b.submit(_req(0)) and b.submit(_req(1))
    assert not b.submit(_req(2))  # budget exhausted -> rejected
    b.next_batch()
    assert b.submit(_req(2))  # drained -> admits again


def test_batcher_config_validation():
    with pytest.raises(ValueError, match="max_batch"):
        BatcherConfig(max_batch=0)
    with pytest.raises(ValueError, match="max_queue_depth"):
        BatcherConfig(max_queue_depth=0)  # would reject every submit
    with pytest.raises(ValueError, match="warm_batch_sizes"):
        BatcherConfig(warm_batch_sizes=(4, 2))
    with pytest.raises(ValueError, match="largest warm batch"):
        BatcherConfig(max_batch=16, warm_batch_sizes=(1, 2, 4, 8))


# -- service -------------------------------------------------------------------


def test_service_results_match_reference_mixed_k():
    svc = _svc()
    reqs = []
    for i, k in enumerate([1, 2, 1, 3]):
        a, b = _rand_a(i), _rand_b(100 + i)
        reqs.append((svc.submit(a, b, k=k), a, b, k))
    assert svc.run_until_drained() == 4
    for rid, a, b, k in reqs:
        c = svc.pop_result(rid)
        expect = a
        for _ in range(k):
            expect = ref.su3_mult_ref(expect, b)
        np.testing.assert_allclose(
            np.asarray(c), np.asarray(expect), rtol=1e-4, atol=1e-4
        )


def test_service_coalesces_same_bucket_into_one_dispatch():
    svc = _svc()
    for i in range(4):
        svc.submit(_rand_a(i), _rand_b(i), k=1)
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 1 and snap["mean_live_batch"] == 4.0
    assert snap["completed"] == 4


def test_service_backpressure_and_metrics():
    svc = _svc(batcher=BatcherConfig(max_queue_depth=2))
    a, b = _rand_a(0), _rand_b(0)
    assert svc.submit(a, b, k=1) is not None
    assert svc.submit(a, b, k=1) is not None
    assert svc.submit(a, b, k=1) is None  # backpressure
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    assert snap["rejected"] == 1 and snap["admitted"] == 2
    assert snap["queue_depth_max"] == 2


def test_service_rejects_malformed_lattice():
    svc = _svc()
    with pytest.raises(ValueError, match="canonical"):
        svc.submit(jnp.zeros((17, 4, 3, 3), jnp.complex64), _rand_b(0))


def test_service_config_rejects_non_planar_layout():
    with pytest.raises(ValueError, match="planar"):
        ServiceConfig(layout=Layout.AOS)
    # the autotune cache only holds SoA-measured tuples
    with pytest.raises(ValueError, match="SoA plans only"):
        ServiceConfig(layout=Layout.AOSOA, autotune=True)
    assert ServiceConfig(layout=Layout.AOSOA, autotune=False).layout == Layout.AOSOA


def test_service_pop_ready_drains_all_results():
    svc = _svc()
    ids = [svc.submit(_rand_a(i), _rand_b(i), k=1) for i in range(3)]
    svc.run_until_drained()
    ready = svc.pop_ready()
    assert sorted(ready) == sorted(ids)
    assert svc.pop_ready() == {}  # drained: nothing retained
    assert not any(svc.has_result(rid) for rid in ids)


def test_pop_ready_leaves_awaited_results_for_arun():
    """A poller draining via pop_ready must not steal an arun's result."""

    async def go():
        svc = _svc()
        pending = asyncio.ensure_future(svc.arun(_rand_a(0), _rand_b(0), k=1))
        drained = {}
        for _ in range(50):
            await asyncio.sleep(0)
            svc.step()
            drained.update(svc.pop_ready())
            if pending.done():
                break
        return await pending, drained

    c, drained = asyncio.run(go())
    assert drained == {}  # the awaited result was delivered by arun, not stolen
    np.testing.assert_allclose(
        np.asarray(c), np.asarray(ref.su3_mult_ref(_rand_a(0), _rand_b(0))),
        rtol=1e-4, atol=1e-4,
    )


def test_service_warm_precompiles_shapes():
    svc = _svc()
    svc.warm((2,), ks=(1,), batch_sizes=(4,))
    svc.metrics.reset()
    for i in range(4):
        svc.submit(_rand_a(i), _rand_b(i), k=1)
    svc.run_until_drained()
    assert svc.metrics.snapshot()["compiles"] == 0  # shape was warmed


def test_service_bf16_storage_within_1e2_of_f32():
    """The acceptance bar: bf16-storage/f32-accumulate vs the f32 path."""
    f32, bf16 = _svc(), _svc(dtype="bfloat16", accum_dtype="float32")
    pairs = [(_rand_a(i), _rand_b(50 + i)) for i in range(3)]
    ids32 = [f32.submit(a, b, k=2) for a, b in pairs]
    ids16 = [bf16.submit(a, b, k=2) for a, b in pairs]
    f32.run_until_drained()
    bf16.run_until_drained()
    for i32, i16 in zip(ids32, ids16):
        c32 = np.asarray(f32.pop_result(i32))
        c16 = np.asarray(bf16.pop_result(i16))
        rel = np.max(np.abs(c16 - c32)) / max(np.max(np.abs(c32)), 1.0)
        assert rel < 1e-2
    # the bf16 pool runs genuinely mixed-precision plans
    plan16 = bf16.runner_for(2).plan
    assert plan16.cfg.dtype == "bfloat16" and plan16.cfg.accum_dtype == "float32"
    assert "+acc-float32" in plan16.describe()


def test_bf16_plan_streams_fewer_hlo_bytes_than_f32():
    f32 = autotune.hlo_bytes_for_variant("pallas", Layout.SOA, n_sites=256, tile=64)
    bf16 = autotune.hlo_bytes_for_variant(
        "pallas", Layout.SOA, n_sites=256, tile=64,
        dtype="bfloat16", accum_dtype="float32",
    )
    assert bf16 < f32
    # canonical variants show the clean 2x storage drop
    xf32 = autotune.hlo_bytes_for_variant("versionX", Layout.SOA, n_sites=256, tile=64)
    xbf16 = autotune.hlo_bytes_for_variant(
        "versionX", Layout.SOA, n_sites=256, tile=64, dtype="bfloat16"
    )
    assert xbf16 < 0.92 * xf32


def test_service_async_face_coalesces():
    async def go():
        svc = _svc()
        outs = await asyncio.gather(
            *[svc.arun(_rand_a(i), _rand_b(i), k=1) for i in range(4)]
        )
        return svc, outs

    svc, outs = asyncio.run(go())
    assert len(outs) == 4
    assert svc.metrics.snapshot()["dispatches"] == 1  # one gather tick, one batch
    for i, c in enumerate(outs):
        np.testing.assert_allclose(
            np.asarray(c), np.asarray(ref.su3_mult_ref(_rand_a(i), _rand_b(i))),
            rtol=1e-4, atol=1e-4,
        )


# -- metrics -------------------------------------------------------------------


def test_metrics_snapshot_schema_and_percentiles():
    m = ServiceMetrics()
    for depth in (1, 2, 3):
        m.record_admit(depth)
    m.record_dispatch(live=3, padded=4, step_s=0.5, flops=864e6 * 3)
    for lat in (0.010, 0.020, 0.100):
        m.record_completion(lat)
    snap = m.snapshot()
    assert snap["admitted"] == 3 and snap["completed"] == 3
    assert snap["latency_p50_ms"] == pytest.approx(20.0)
    assert snap["latency_p99_ms"] == pytest.approx(100.0, rel=0.05)
    assert snap["mean_batch_occupancy"] == pytest.approx(0.75)
    assert snap["padded_slot_fraction"] == pytest.approx(0.25)
    assert snap["sustained_gflops_busy"] == pytest.approx(864e6 * 3 / 0.5 / 1e9)
    assert snap["queue_depth_max"] == 3
    m.reset()
    empty = m.snapshot()
    assert empty["completed"] == 0 and empty["latency_p99_ms"] == 0.0


# -- solve requests (data-dependent iteration count) ---------------------------


def _solve_problem(L=2):
    return autotune._cg_measure_problem(L)


def test_submit_solve_result_matches_reference():
    from repro.core.su3.plan import CG_SHIFT, cg_reference_solve

    svc = _svc(solve_iters_per_step=2)
    u, b = _solve_problem()
    rid = svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    assert rid is not None
    svc.run_until_drained()
    x = svc.pop_result(rid)
    x_ref, _, ok = cg_reference_solve(u, b, 2, sigma=CG_SHIFT, tol=1e-6,
                                      max_iters=64)
    assert ok
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-5)


def test_solve_validation_and_admit_metrics():
    svc = _svc()
    u, b = _solve_problem()
    with pytest.raises(ValueError, match="canonical"):
        svc.submit_solve(u, jnp.zeros((3,), jnp.complex64))
    with pytest.raises(ValueError, match="max_iters"):
        svc.submit_solve(u, b, max_iters=0)
    with pytest.raises(ValueError, match="tol"):
        svc.submit_solve(u, b, tol=-1.0)
    assert svc.submit_solve(u, b) is not None
    assert svc.metrics.snapshot()["admitted"] == 1
    svc.run_until_drained()


def test_solve_retires_midstream_and_frees_budget():
    """One long solve + a multiply stream on the same host: the rotation
    keeps multiplies completing WHILE the solve is in flight, the solve
    retires on its residual test (not max_iters), and a multiply submitted
    AFTER retirement is served immediately — the budget is free again."""
    svc = _svc(solve_iters_per_step=2)
    u, b = _solve_problem()
    sid = svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    mids = [svc.submit(_rand_a(i), _rand_b(i), k=1) for i in range(3)]
    solve_done_at = None
    mult_done_mid_solve = 0
    steps = 0
    while svc.pending():
        steps += 1
        svc.step()
        for rid in list(svc.pop_ready()):
            if rid == sid:
                solve_done_at = steps
            elif solve_done_at is None:
                mult_done_mid_solve += 1
    assert solve_done_at is not None
    assert mult_done_mid_solve >= 1  # multiplies flowed during the solve
    snap = svc.metrics.snapshot()
    assert 0 < snap["kind_iterations"]["solve"] < 64  # retired early
    # the freed budget serves new traffic in one step
    rid = svc.submit(_rand_a(9), _rand_b(9), k=1)
    svc.step()
    assert rid in svc.pop_ready()


def test_solve_kind_rotation_non_starving():
    """All three kinds pending at once: the rotation serves each in turn,
    so every kind completes and none waits for the others to drain."""
    svc = _svc(solve_iters_per_step=1)
    u, b = _solve_problem()
    n = 16
    v = jax.random.normal(jax.random.PRNGKey(3), (n, 3, 2))
    sid = svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    tid = svc.submit_stencil(u, jax.lax.complex(v[..., 0], v[..., 1]))
    mid = svc.submit(_rand_a(0), _rand_b(0), k=1)
    done_step: dict[int, int] = {}
    steps = 0
    while svc.pending():
        steps += 1
        svc.step()
        for rid in svc.pop_ready():
            done_step[rid] = steps
    assert set(done_step) == {sid, tid, mid}
    # with one solve iteration per turn the solve needs many turns; the
    # other kinds must NOT be starved behind it
    assert done_step[mid] < done_step[sid]
    assert done_step[tid] < done_step[sid]
    snap = svc.metrics.snapshot()
    assert snap["completed"] == 3
    # one iteration per turn: the iteration metric counts every solve turn
    assert snap["kind_iterations"]["solve"] >= 2


def test_solve_per_kind_iteration_metrics():
    svc = _svc(solve_iters_per_step=4)
    u, b = _solve_problem()
    svc.submit_solve(u, b, tol=1e-6, max_iters=64)
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    ki = snap["kind_iterations"]
    assert set(ki) == {"solve"} and ki["solve"] > 0
    # dispatched in full 4-iteration turns: the solve converges before
    # max_iters, so no turn is cut short
    assert ki["solve"] == 4 * snap["dispatches"]


# -- host-sharded pool over the simulated host topology -----------------------


def test_multihost_service_routes_by_locality():
    svc = SU3Service(ServiceConfig(autotune=False, tile=16, hosts=2))
    ids = [svc.submit(_rand_a(i), _rand_b(i), k=1) for i in range(2)]  # L=2
    ids.append(svc.submit(_rand_a(9, n_sites=256), _rand_b(9), k=1))  # L=4
    svc.run_until_drained()
    # the two Ls landed on different hosts; pool keys carry the host
    assert {key[0] for key in svc.pool_keys()} == {0, 1}
    assert set(svc.router.assignments()) == {2, 4}
    snap = svc.metrics.snapshot()
    assert set(snap["host_dispatches"]) == {"0", "1"}
    for rid in ids:
        assert svc.pop_result(rid) is not None


def test_multihost_warm_spreads_pool_across_hosts():
    """warm() is a burst of first-sight Ls with no traffic in between; the
    router's nominal placement charge must still spread them (a zero-load
    tie would pin every warmed L — and so all future traffic — to host 0)."""
    svc = SU3Service(ServiceConfig(autotune=False, tile=16, hosts=2))
    svc.warm((2, 4))
    assert {key[0] for key in svc.pool_keys()} == {0, 1}
    homes = svc.router.assignments()
    assert homes[2] != homes[4]


def test_multihost_rejects_explicit_mesh():
    with pytest.raises(ValueError, match="EITHER"):
        SU3Service(ServiceConfig(autotune=False, tile=16, hosts=2), mesh=object())


def test_service_config_validation():
    with pytest.raises(ValueError, match="hosts"):
        ServiceConfig(autotune=False, tile=16, hosts=0)


# -- the multiply path's contracts ---------------------------------------------


def _same_bits(x, y) -> bool:
    x, y = np.ascontiguousarray(x), np.ascontiguousarray(y)
    return (x.shape == y.shape and x.dtype == y.dtype
            and np.array_equal(x.view(np.uint8), y.view(np.uint8)))


def _su3(n, seed):
    """``n`` random SU(3) matrices (QR of a complex Gaussian, unit det)."""
    rng = np.random.default_rng(seed)
    q, r = np.linalg.qr(rng.standard_normal((n, 3, 3))
                        + 1j * rng.standard_normal((n, 3, 3)))
    d = np.diagonal(r, axis1=-2, axis2=-1)
    q = q * (d / np.abs(d))[..., None, :]
    return (q / np.linalg.det(q)[..., None, None] ** (1 / 3)).astype(np.complex64)


def _chain_ref(a, b, k):
    for _ in range(k):
        a = ref.su3_mult_ref(a, b)
    return a


@pytest.fixture(scope="module")
def warm4_service():
    """A service whose every batch pads to 4, shared so that its compiled
    programs serve each case."""
    return _svc(batcher=BatcherConfig(max_batch=4, warm_batch_sizes=(4,)))


@pytest.mark.parametrize("live", [1, 2, 3])
def test_padded_batch_rows_never_leak(warm4_service, live):
    """A batch padded to warm size 4 with zero rows: the live results equal
    each request dispatched alone, bit for bit, only the live requests
    complete, and the metrics charge the padded rows."""
    svc = warm4_service
    svc.metrics.reset()
    reqs = [(_rand_a(40 + i), _rand_b(50 + i)) for i in range(live)]
    ids = [svc.submit(a, b, k=2) for a, b in reqs]
    assert svc.run_until_drained() == live
    runner = svc.runner_for(2)
    for rid, (a, b) in zip(ids, reqs):
        alone = runner.multiply(np.asarray(a)[None], np.asarray(b)[None], k=2)[0]
        assert _same_bits(svc.pop_result(rid), alone)
    assert svc.pop_ready() == {}
    snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 1 and snap["completed"] == live
    assert svc.metrics.padded_slots == 4 - live
    assert snap["mean_batch_occupancy"] == pytest.approx(live / 4)


@pytest.mark.parametrize("hosts", [1, 2])
def test_mixed_L_stream_one_dispatch_per_bucket(hosts):
    """A mixed-L, mixed-k stream of SU(3) fields: every (L, k) bucket is one
    dispatch, each result is within the plan's tolerance of the chained
    reference, and on two hosts each L runs on its router home."""
    svc = _svc(hosts=hosts)
    stream = [(2, 2), (4, 1), (2, 3), (4, 2)]
    reqs = []
    for i, (L, k) in enumerate(stream):
        a = _su3(L**4 * 4, 60 + i).reshape(L**4, 4, 3, 3)
        b = _su3(4, 70 + i)
        reqs.append((svc.submit(a, b, k=k), a, b, k))
    assert svc.run_until_drained() == len(stream)
    tol = verify_tolerance("float32")
    for rid, a, b, k in reqs:
        err = float(jnp.max(jnp.abs(svc.pop_result(rid) - _chain_ref(a, b, k))))
        assert err < tol, (k, err)
    snap = svc.metrics.snapshot()
    assert snap["dispatches"] == len(set(stream))
    homes = svc.router.assignments()
    assert {key[0] for key in svc.pool_keys()} == set(homes.values())
    for key in svc.pool_keys():
        assert key[0] == homes[key[1]]
    if hosts == 2:
        assert homes[2] != homes[4]
        assert snap["host_dispatches"] == {"0": 2, "1": 2}


def test_solve_completes_alongside_batched_multiply_stream():
    """A CG solve (data-dependent turn count) shares the host with a
    multiply stream that keeps arriving: multiplies complete while the
    solve is in flight, the solve retires on its residual test, and every
    request of both kinds has the right answer."""
    from repro.core.su3.plan import CG_SHIFT, cg_reference_solve

    svc = _svc(solve_iters_per_step=2)
    u, b = _solve_problem()
    # the solve shares the multiplies' (tenant, class) group, so their turns
    # rotate multiply -> solve
    sid = svc.submit_solve(u, b, tol=1e-6, max_iters=64, slo="bulk")
    mult = [(svc.submit(_rand_a(i), _rand_b(i), k=k), i, k)
            for i, k in enumerate([1, 2, 1])]
    results = {}
    while svc.pending():
        svc.step()
        results.update(svc.pop_ready())
        if sid not in results and len(results) == len(mult):
            # every multiply so far retired while the solve is in flight:
            # one more arrives behind them
            mult.append((svc.submit(_rand_a(7), _rand_b(7), k=1), 7, 1))
    assert sid in results and len(results) == len(mult) + 1
    assert len(mult) > 3  # a multiply arrived, and finished, mid-solve
    for rid, seed, k in mult:
        np.testing.assert_allclose(
            np.asarray(results[rid]),
            np.asarray(_chain_ref(_rand_a(seed), _rand_b(seed), k)),
            rtol=1e-4, atol=1e-4)
    x_ref, _, ok = cg_reference_solve(u, b, 2, sigma=CG_SHIFT, tol=1e-6,
                                      max_iters=64)
    assert ok
    np.testing.assert_allclose(np.asarray(results[sid]), np.asarray(x_ref),
                               rtol=1e-5, atol=1e-5)
    assert 0 < svc.metrics.snapshot()["kind_iterations"]["solve"] < 64


def test_deadline_expired_in_queue_does_not_block_its_bucket():
    """An expired request is swept out of its (L, k) bucket with a
    structured timeout; the request queued behind it in the same bucket is
    dispatched alone and equals a clean dispatch of it bit for bit."""
    svc = _svc()
    a0, b0 = _rand_a(80), _rand_b(80)
    a1, b1 = _rand_a(81), _rand_b(81)
    dead = svc.submit(a0, b0, k=2, deadline_s=1e-4)
    time.sleep(0.01)
    live = svc.submit(a1, b1, k=2)
    assert svc.run_until_drained() == 1
    assert isinstance(svc.pop_result(dead), DeadlineExceededError)
    clean = svc.runner_for(2).multiply(np.asarray(a1)[None],
                                       np.asarray(b1)[None], k=2)[0]
    assert _same_bits(svc.pop_result(live), clean)
    snap = svc.metrics.snapshot()
    assert snap["timeouts"] == 1 and snap["dispatches"] == 1
    assert snap["mean_live_batch"] == 1.0
