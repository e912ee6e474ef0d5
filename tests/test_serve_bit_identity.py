"""The served multiply's bit-identity contract.

Every request of a coalesced batch, its codec on the device
(``BatchedLatticeRunner.multiply``), comes back bit for bit what
``plan.fused_step(k)`` returns for it alone with the host codec
(``_pack_on_host``, ``pack_links_on_host``, ``plan.unpack``): for both planar
layouts, float32, bfloat16 and bfloat16 with float32 accumulation, and the
served cell's k walk.  Each (plan, k) compiles the unrolled chain twice in
interpret mode, so this grid has a file of its own.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # smoke's fast tier skips these (-m "not slow")

import jax
import jax.numpy as jnp

from repro.core.su3.layouts import Layout
from repro.serve.su3 import ServiceConfig, SU3Service


def _rand(seed, shape):
    g = jax.random.normal(jax.random.PRNGKey(seed), shape + (2,))
    return jax.lax.complex(g[..., 0], g[..., 1])


def _same_bits(x, y) -> bool:
    x, y = np.ascontiguousarray(x), np.ascontiguousarray(y)
    return (x.shape == y.shape and x.dtype == y.dtype
            and np.array_equal(x.view(np.uint8), y.view(np.uint8)))


def _plan_chain_on_host_codec(runner, a, b, k):
    """``plan.fused_step(k)`` on one request, packed and unpacked by the
    host codec — the oracle the served (device-codec) path must equal."""
    plan = runner.plan
    a_phys = jnp.asarray(runner._pack_on_host(np.asarray(a)[None])[0])
    b_p = jnp.asarray(plan.pack_links_on_host(np.asarray(b)))
    return plan.unpack(plan.fused_step(k)(a_phys, b_p), a.shape[0])


_PLANS = [
    (Layout.SOA, "float32", ""),
    (Layout.AOSOA, "float32", ""),
    (Layout.SOA, "bfloat16", ""),
    (Layout.AOSOA, "bfloat16", ""),
    (Layout.SOA, "bfloat16", "float32"),
    (Layout.AOSOA, "bfloat16", "float32"),
]


@pytest.fixture(scope="module")
def plan_services():
    """One service per plan, so its compiled codec serves every k."""
    return {}


@pytest.mark.parametrize("k", [1, 2, 4, 8])
@pytest.mark.parametrize("layout,dtype,accum", _PLANS)
def test_served_multiply_bit_identical_to_plan_chain(plan_services, layout,
                                                     dtype, accum, k):
    """Two requests of one bucket ride ONE coalesced dispatch, and each
    comes back bit for bit what ``plan.fused_step(k)`` returns for it alone
    on the host codec."""
    key = (layout, dtype, accum)
    if key not in plan_services:
        plan_services[key] = SU3Service(ServiceConfig(
            autotune=False, tile=16, layout=layout, dtype=dtype,
            accum_dtype=accum))
    svc = plan_services[key]
    svc.metrics.reset()
    reqs = [(_rand(10 * k + i, (16, 4, 3, 3)), _rand(20 * k + i, (4, 3, 3)))
            for i in range(2)]
    ids = [svc.submit(a, b, k=k) for a, b in reqs]
    assert svc.run_until_drained() == 2
    snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 1 and snap["mean_live_batch"] == 2.0
    runner = svc.runner_for(2)
    for rid, (a, b) in zip(ids, reqs):
        got = svc.pop_result(rid)
        assert _same_bits(got, _plan_chain_on_host_codec(runner, a, b, k))
