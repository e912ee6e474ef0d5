"""ExecutionPlan layer: placement equivalence, fused stepping, registry,
batched lattice serving, and the persistent autotune cache."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.su3 import layouts, plan, registry
from repro.core.su3.engine import EngineConfig, SU3Engine
from repro.core.su3.layouts import Layout
from repro.kernels import ref


def _random_lattice(key, n_sites):
    a = jax.random.normal(key, (n_sites, 4, 3, 3, 2))
    return jax.lax.complex(a[..., 0], a[..., 1])


def _random_b(key):
    b = jax.random.normal(key, (4, 3, 3, 2))
    return jax.lax.complex(b[..., 0], b[..., 1])


# -- placement-policy equivalence --------------------------------------------


@pytest.mark.parametrize("variant,layout", [("pallas", Layout.SOA), ("versionX", Layout.AOS)])
def test_placement_policies_bit_identical(variant, layout):
    """sharded / host_scatter / replicated must produce bit-identical verified C."""
    results = {}
    for placement in plan.PLACEMENTS:
        cfg = EngineConfig(L=4, layout=layout, variant=variant, placement=placement,
                           iterations=1, warmups=0, tile=128)
        p = plan.build_plan(cfg)
        a_phys, b_p, _, _ = p.init_data()
        c = p.step(a_phys, b_p)
        assert p.verify(c), placement
        results[placement] = np.asarray(jax.device_get(c))
    base = results["sharded"]
    for placement, arr in results.items():
        np.testing.assert_array_equal(arr, base, err_msg=placement)


# -- fused multi-iteration stepping ------------------------------------------


@pytest.mark.parametrize("variant,layout", [
    ("pallas", Layout.SOA), ("pallas", Layout.AOSOA), ("versionX", Layout.SOA),
])
@pytest.mark.parametrize("k", [2, 4, 12])  # 12 exercises the fori_loop (>_UNROLL_MAX) path
def test_fused_step_matches_k_sequential(variant, layout, k):
    cfg = EngineConfig(L=2, layout=layout, variant=variant, tile=16,
                       iterations=1, warmups=0)
    p = plan.build_plan(cfg)
    codec = p.codec
    a = _random_lattice(jax.random.PRNGKey(3), p.padded_sites)
    b = _random_b(jax.random.PRNGKey(4))
    a_phys, b_p = codec.pack(a), codec.pack_b(b)
    x = a_phys
    for _ in range(k):
        x = p.step(x, b_p)
    fused = p.fused_step(k)(a_phys, b_p)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(fused)), np.asarray(jax.device_get(x)),
        rtol=1e-5, atol=1e-5,
    )


def test_engine_run_fused_verifies():
    cfg = EngineConfig(L=4, iterations=3, warmups=1, tile=128)
    r = SU3Engine(cfg).run_fused(k=3)
    assert r.verified and r.fused_k == 3
    assert all(t > 0 for t in r.iter_seconds)


# -- registry + plan validation ----------------------------------------------


def test_registry_unifies_variants_and_pallas():
    names = registry.kernel_names()
    assert "pallas" in names and "versionX" in names and "version_gemm" in names
    entry = registry.get_kernel("pallas")
    assert entry.form == registry.PLANAR and entry.supports_fused
    assert registry.kernel_names(backend="pallas") == [
        "pallas", "pallas_cg", "pallas_megakernel", "pallas_stencil"]
    assert "pallas" not in registry.kernel_names(form=registry.CANONICAL)
    assert registry.kernel_names(form=registry.BATCHED) == ["pallas_megakernel"]
    assert registry.kernel_names(form=registry.STENCIL) == ["pallas_stencil"]
    assert registry.kernel_names(form=registry.STENCIL_AXPY) == ["pallas_cg"]


def test_plan_rejects_invalid_combinations():
    with pytest.raises(ValueError, match="layout"):
        plan.build_plan(EngineConfig(L=2, layout=Layout.AOS, variant="pallas", tile=16))
    with pytest.raises(KeyError, match="unknown SU3 kernel"):
        plan.build_plan(EngineConfig(L=2, variant="nope", tile=16))
    with pytest.raises(ValueError, match="placement"):
        plan.build_plan(EngineConfig(L=2, tile=16, placement="socket0"))


def test_codec_dedups_unpack_paths():
    """One codec handles padded and sliced unpack for every layout."""
    for layout in Layout:
        codec = layouts.make_codec(layout, tile=16)
        a = _random_lattice(jax.random.PRNGKey(7), 32)
        phys = codec.pack(a)
        np.testing.assert_allclose(
            np.asarray(codec.unpack(phys, 30)), np.asarray(a[:30]), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(codec.unpack(phys)), np.asarray(a), atol=1e-6)


# -- batched lattice serving --------------------------------------------------


def test_batched_lattice_runner_matches_reference():
    runner = plan.BatchedLatticeRunner(EngineConfig(L=2, tile=16))
    B, S = 3, 16
    a = jnp.stack([_random_lattice(jax.random.PRNGKey(i), S) for i in range(B)])
    b = jnp.stack([_random_b(jax.random.PRNGKey(100 + i)) for i in range(B)])
    c = runner.multiply(a, b)
    for i in range(B):
        np.testing.assert_allclose(
            np.asarray(c[i]), np.asarray(ref.su3_mult_ref(a[i], b[i])),
            rtol=1e-4, atol=1e-4,
        )


def test_batched_lattice_runner_fused_chain():
    runner = plan.BatchedLatticeRunner(EngineConfig(L=2, tile=16))
    B, S = 2, 16
    a = jnp.stack([_random_lattice(jax.random.PRNGKey(i), S) for i in range(B)])
    b = jnp.stack([_random_b(jax.random.PRNGKey(50 + i)) for i in range(B)])
    fused = runner.multiply(a, b, k=3)
    seq = a
    for _ in range(3):
        seq = jnp.stack([ref.su3_mult_ref(seq[i], b[i]) for i in range(B)])
    np.testing.assert_allclose(np.asarray(fused), np.asarray(seq), rtol=1e-4, atol=1e-4)


def test_canonical_arrays_stay_on_the_host():
    # a TPU pads the canonical (S, 4, 3, 3) form's minor dims to 128 lanes:
    # packing and unpacking run on the host CPU, only physical forms are
    # placed where the plan shards them
    p = plan.build_plan(EngineConfig(L=2, tile=16))
    cpu = jax.devices("cpu")[0]
    a = np.asarray(_random_lattice(jax.random.PRNGKey(3), 16))
    a_phys = p.pack_gauge(a)
    assert a_phys.sharding == p.sharding
    b_p = p.pack_links(np.asarray(_random_b(jax.random.PRNGKey(4))))
    assert b_p.sharding == p.replicated and b_p.shape == (2, 36)
    c = p.unpack(p.step(a_phys, b_p))
    assert c.committed and c.devices() == {cpu}
    assert bool(jnp.array_equal(p.unpack(a_phys), a))
    seen = []
    out = layouts.on_host(lambda x, n: seen.append(n) or x + 1, jnp.ones(3), 7)
    assert seen == [7] and out.devices() == {cpu}


# -- mixed-precision (bf16-storage / f32-accumulate) plans ---------------------
# (persistent autotune cache coverage lives in tests/test_autotune_cache.py)


def test_bf16_accum_plan_matches_f32_and_verifies():
    a = _random_lattice(jax.random.PRNGKey(11), 16)
    b = _random_b(jax.random.PRNGKey(12))
    p32 = plan.build_plan(EngineConfig(L=2, tile=16))
    p16 = plan.build_plan(
        EngineConfig(L=2, tile=16, dtype="bfloat16", accum_dtype="float32")
    )
    c32 = np.asarray(p32.codec.unpack(p32.step(p32.codec.pack(a), p32.codec.pack_b(b))))
    c16 = np.asarray(p16.codec.unpack(p16.step(p16.codec.pack(a), p16.codec.pack_b(b))))
    rel = np.max(np.abs(c16 - c32)) / np.max(np.abs(c32))
    assert rel < 1e-2  # storage rounding only; the FMA chain accumulated in f32
    # canonical verification + fused chain through the mixed plan
    a_phys, b_p, _, _ = p16.init_data()
    assert p16.verify(p16.step(a_phys, b_p))
    assert p16.verify(p16.fused_step(3)(a_phys, b_p))
    assert p16.cfg.is_mixed_precision and p16.cfg.word_bytes == 2


def test_mixed_precision_requires_kernel_accum_support():
    name = "_planar_no_accum_test"
    registry.register_kernel(
        name, layouts=(Layout.SOA,), backends=("pallas",),
        form=registry.PLANAR, supports_fused=True,
    )(lambda a_p, b_p, **kw: a_p)
    try:
        with pytest.raises(ValueError, match="accumulate"):
            plan.build_plan(EngineConfig(
                L=2, tile=16, variant=name,
                dtype="bfloat16", accum_dtype="float32",
            ))
    finally:
        registry._KERNELS.pop(name, None)
    # canonical kernels accumulate in f32 by construction: no error
    p = plan.build_plan(EngineConfig(
        L=2, tile=16, variant="versionX",
        dtype="bfloat16", accum_dtype="float32",
    ))
    a_phys, b_p, _, _ = p.init_data()
    assert p.verify(p.step(a_phys, b_p))
