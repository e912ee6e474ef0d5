"""Batched K-chain megakernel: bit-identity, slot-table service dispatch.

Bit-identity contract (all planar layouts x dtypes, CPU interpret):

  * slot_k in {0, 1} — the serving iteration granularity — is bit-identical
    to the chained single-step path (``plan.step`` per slot; dead slots pass
    through untouched).  This is the path the megakernel replaces in
    continuous serving.
  * deep per-slot chains at PURE storage dtypes are bit-identical to the
    same number of sequential single steps (identical FMA order per
    multiply).
  * deep MIXED-PRECISION chains are bit-identical to the fused in-kernel
    chain (``plan.fused_step(k)``): both upcast once, chain at the
    accumulate width, and narrow once — sequential single steps round
    through storage between multiplies, which is a different (worse)
    numerical contract, not a megakernel bug.

(For f32, deep megakernel chains match sequential steps rather than the
unrolled fused chain: the dynamic per-slot trip count compiles to a loop, so
XLA's FMA contraction differs from the straight-line unrolled body at the
last ulp.  Every multiply is still the exact single-step computation.)
"""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # smoke's fast tier skips these (-m "not slow")

import jax
import jax.numpy as jnp

from repro.core.su3 import registry
from repro.core.su3.layouts import Layout
from repro.core.su3.plan import (
    EngineConfig,
    MEGAKERNEL_VARIANT,
    build_plan,
    make_raw_batched_step,
    make_raw_step,
)
from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service

SLOTS = 4


def _rand_batch(plan, slots, seed=0):
    rng = np.random.default_rng(seed)
    S = plan.padded_sites
    a = rng.standard_normal((slots, S, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((slots, 4, 3, 3, 2)).astype(np.float32)
    a = jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64)
    b = jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64)
    return jax.vmap(plan.codec.pack)(a), jax.vmap(plan.codec.pack_b)(b)


def _plan(layout, dtype="float32", accum=""):
    cfg = EngineConfig(L=2, dtype=dtype, layout=layout, tile=16,
                       accum_dtype=accum)
    return build_plan(cfg)


ALL_PLANS = [
    (Layout.SOA, "float32", ""),
    (Layout.AOSOA, "float32", ""),
    (Layout.SOA, "bfloat16", ""),
    (Layout.AOSOA, "bfloat16", ""),
    (Layout.SOA, "bfloat16", "float32"),
    (Layout.AOSOA, "bfloat16", "float32"),
]


@pytest.mark.parametrize("layout,dtype,accum", ALL_PLANS)
def test_iteration_granularity_bit_identical_to_single_step(layout, dtype, accum):
    """slot_k in {0,1} — what continuous serving dispatches — must equal the
    chained single-step path bit for bit, dead slots passing through."""
    plan = _plan(layout, dtype, accum)
    a_phys, b_p = _rand_batch(plan, SLOTS)
    ks = jnp.array([0, 1, 1, 0], jnp.int32)
    # the step donates its slot table; the reference below reads a_phys
    c = plan.fused_batched_step(SLOTS, max_k=4)(jnp.copy(a_phys), b_p, ks)
    ref = jnp.stack([
        plan.step(a_phys[s], b_p[s]) if int(ks[s]) else a_phys[s]
        for s in range(SLOTS)
    ])
    assert c.dtype == ref.dtype
    assert bool(jnp.all(c == ref))


@pytest.mark.parametrize("layout,dtype", [
    (Layout.SOA, "float32"), (Layout.AOSOA, "float32"),
    (Layout.SOA, "bfloat16"), (Layout.AOSOA, "bfloat16"),
])
def test_deep_chains_pure_dtype_bit_identical_to_sequential_steps(layout, dtype):
    plan = _plan(layout, dtype)
    a_phys, b_p = _rand_batch(plan, SLOTS)
    ks = jnp.array([1, 2, 3, 4], jnp.int32)
    # the step donates its slot table; the reference below reads a_phys
    c = plan.fused_batched_step(SLOTS, max_k=4)(jnp.copy(a_phys), b_p, ks)
    ref = []
    for s in range(SLOTS):
        x = a_phys[s]
        for _ in range(int(ks[s])):
            x = plan.step(x, b_p[s])
        ref.append(x)
    assert bool(jnp.all(c == jnp.stack(ref)))


@pytest.mark.parametrize("layout", [Layout.SOA, Layout.AOSOA])
def test_deep_chains_mixed_precision_bit_identical_to_fused_step(layout):
    plan = _plan(layout, "bfloat16", "float32")
    a_phys, b_p = _rand_batch(plan, SLOTS)
    ks = jnp.array([1, 2, 3, 4], jnp.int32)
    # the step donates its slot table; the reference below reads a_phys
    c = plan.fused_batched_step(SLOTS, max_k=4)(jnp.copy(a_phys), b_p, ks)
    ref = jnp.stack([
        plan.fused_step(int(ks[s]))(a_phys[s], b_p[s]) for s in range(SLOTS)
    ])
    assert bool(jnp.all(c == ref))


def test_slot_k_clamped_to_static_max():
    plan = _plan(Layout.SOA)
    a_phys, b_p = _rand_batch(plan, 2)
    c = plan.fused_batched_step(2, max_k=2)(
        jnp.copy(a_phys), b_p, jnp.array([5, 2], jnp.int32))
    ref = plan.fused_batched_step(2, max_k=2)(
        a_phys, b_p, jnp.array([2, 2], jnp.int32))
    assert bool(jnp.all(c == ref))


def test_batched_kernel_is_registered_and_gated():
    entry = registry.get_kernel(MEGAKERNEL_VARIANT)
    assert entry.form == registry.BATCHED
    assert entry.supports_fused and entry.supports_accum
    assert MEGAKERNEL_VARIANT in registry.kernel_names(form=registry.BATCHED)
    # a batched kernel cannot be a plan's single-lattice step...
    codec = _plan(Layout.SOA).codec
    with pytest.raises(ValueError, match="fused_batched_step"):
        make_raw_step(codec, entry, tile=16)
    # ...and the batched step builder rejects non-batched kernels
    with pytest.raises(ValueError, match="batched"):
        make_raw_batched_step(
            codec, registry.get_kernel("pallas"), tile=16, max_k=2)


def test_fused_batched_step_rejects_bad_args():
    plan = _plan(Layout.SOA)
    with pytest.raises(ValueError, match="slots"):
        plan.fused_batched_step(0)
    with pytest.raises(ValueError, match="max_k"):
        plan.fused_batched_step(2, max_k=0)


# -- service slot-table dispatch ----------------------------------------------


def _mega_service(slots=4, horizon=1, hosts=1, max_queue_depth=64):
    return SU3Service(ServiceConfig(
        autotune=False, tile=16, continuous=True, megakernel=True,
        chain_slots=slots, chain_horizon=horizon, hosts=hosts,
        batcher=BatcherConfig(max_batch=slots, warm_batch_sizes=(slots,),
                              max_queue_depth=max_queue_depth),
    ))


def _rand_req(rng, n_sites):
    a = rng.standard_normal((n_sites, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((4, 3, 3, 2)).astype(np.float32)
    return (jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64),
            jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64))


def _chain_ref(a, b, k):
    x = a
    for _ in range(k):
        x = jnp.einsum("sjkl,jlm->sjkm", x, b)
    return x


def test_megakernel_requires_continuous():
    with pytest.raises(ValueError, match="continuous"):
        ServiceConfig(megakernel=True)
    with pytest.raises(ValueError, match="chain_horizon"):
        ServiceConfig(continuous=True, megakernel=True, chain_horizon=0)


def test_one_dispatch_per_host_per_iteration_mixed_L():
    """The acceptance bar: mixed lattice sizes and chain depths in flight,
    yet every iteration costs exactly ONE host dispatch (the per-(L, chain)
    dispatch tax collapses into the slot table)."""
    svc = _mega_service(slots=4)
    rng = np.random.default_rng(0)
    reqs = [(2, 1), (2, 3), (3, 2), (2, 2), (3, 1)]
    ids, expect = [], []
    for L, k in reqs:
        a, b = _rand_req(rng, L**4)
        ids.append(svc.submit(a, b, k=k))
        expect.append(_chain_ref(a, b, k))
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    assert snap["completed"] == len(reqs)
    assert snap["dispatches_per_iteration"] == 1.0
    assert snap["host_dispatches"] == {"0": snap["dispatches"]}
    assert snap["midchain_admits"] >= 1  # the 5th request slot-swapped in
    for rid, exp in zip(ids, expect):
        got = svc.pop_result(rid)
        assert float(jnp.max(jnp.abs(got - exp))) < 1e-4


def test_slot_table_grows_for_larger_L_preserving_inflight_state():
    """A bigger lattice arriving mid-flight grows the table capacity; live
    slots re-seat at their mid-chain state and finish correctly."""
    svc = _mega_service(slots=3)
    rng = np.random.default_rng(1)
    a2, b2 = _rand_req(rng, 2**4)
    rid2 = svc.submit(a2, b2, k=3)
    assert svc.step() == 0  # L=2 chain in flight, 2 multiplies to go
    cap_before = svc._tables[0][1].cap_L
    a3, b3 = _rand_req(rng, 3**4)
    rid3 = svc.submit(a3, b3, k=1)
    svc.run_until_drained()
    assert svc._tables[0][1].cap_L == 3 and cap_before == 2
    assert float(jnp.max(jnp.abs(svc.pop_result(rid2) - _chain_ref(a2, b2, 3)))) < 1e-4
    assert float(jnp.max(jnp.abs(svc.pop_result(rid3) - _chain_ref(a3, b3, 1)))) < 1e-4


def test_chain_horizon_amortizes_dispatches():
    """horizon=4 finishes a k=4 request in ONE dispatch where horizon=1
    takes four — the in-kernel chain depth doing the amortizing."""
    rng = np.random.default_rng(2)
    a, b = _rand_req(rng, 2**4)

    svc1 = _mega_service(slots=2, horizon=1)
    rid = svc1.submit(a, b, k=4)
    svc1.run_until_drained()
    one = svc1.pop_result(rid)
    assert svc1.metrics.dispatches == 4

    svc4 = _mega_service(slots=2, horizon=4)
    rid = svc4.submit(a, b, k=4)
    svc4.run_until_drained()
    four = svc4.pop_result(rid)
    assert svc4.metrics.dispatches == 1
    # f32 chains are the same computation either way (see module docstring)
    assert bool(jnp.all(one == four))


def test_megakernel_multihost_routes_and_dispatches_per_host():
    svc = _mega_service(slots=2, hosts=2)
    rng = np.random.default_rng(3)
    ids = {}
    for L in (2, 3):  # router pins each L to its own host
        a, b = _rand_req(rng, L**4)
        ids[L] = (svc.submit(a, b, k=2), _chain_ref(a, b, 2))
    svc.run_until_drained()
    snap = svc.metrics.snapshot()
    assert set(snap["host_dispatches"]) == {"0", "1"}
    for L, (rid, exp) in ids.items():
        assert float(jnp.max(jnp.abs(svc.pop_result(rid) - exp))) < 1e-4


def test_megakernel_warm_compiles_the_table_shape():
    svc = _mega_service(slots=2)
    svc.warm((2,))
    assert ("mega", 2, 2, 1) in svc._seen_shapes
    rng = np.random.default_rng(4)
    a, b = _rand_req(rng, 2**4)
    svc.submit(a, b, k=1)
    svc.run_until_drained()
    assert svc.metrics.compiles == 0, "warmed table shape must not recompile"


def test_dispatch_profiler_megakernel_row_survives_donation():
    """scripts/profile_dispatch.py times the donating megakernel many times
    over one slot table; each call must advance a live buffer."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "profile_dispatch",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "profile_dispatch.py"))
    profile_dispatch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(profile_dispatch)
    row = profile_dispatch.megakernel_amortization_row(2, reps=2)
    assert row["dispatches_per_iter_megakernel"] == 1
    assert row["megakernel_s"] > 0 and row["chains_s"] > 0
