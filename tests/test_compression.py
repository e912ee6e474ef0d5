"""Two-row compressed gauge: kernel/plan/engine level correctness.

The codec-level pack/unpack properties live in
``test_layout_codec_roundtrip.py``; here the compressed PATH is exercised —
the Pallas multiply and stencil kernels and the served multiply streaming
(2, 24, S) gauge blocks with in-register third-row reconstruction — against
the 18-real full-width kernels on the same canonical data.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.su3 import layouts, registry
from repro.core.su3.engine import EngineConfig as _EngineConfig  # noqa: F401
from repro.core.su3.engine import SU3Engine
from repro.core.su3.layouts import Layout
from repro.core.su3.plan import (
    EngineConfig, build_plan, make_raw_step, verify_tolerance)

_TILE = 32
_SITES = 64


def _su3(n_sites: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n_sites, 4, 3, 3)) + 1j * rng.standard_normal(
        (n_sites, 4, 3, 3))
    q, r = np.linalg.qr(g)
    q = q * (np.diagonal(r, axis1=-2, axis2=-1)
             / np.abs(np.diagonal(r, axis1=-2, axis2=-1)))[..., None, :]
    return q / np.linalg.det(q)[..., None, None] ** (1.0 / 3.0)


def _steps(compression: str):
    codec = layouts.make_codec(Layout.SOA, tile=_TILE, compression=compression)
    step = make_raw_step(codec, registry.get_kernel("pallas"), tile=_TILE)
    return codec, step


def test_compressed_multiply_matches_full_kernel_on_su3():
    """C = A x B through the compressed kernel agrees with the full-width
    kernel to f32 reconstruction accuracy when A, B are genuine SU(3) (so
    the product rows the compressed path reconstructs are exact group
    elements)."""
    a = jnp.asarray(_su3(_SITES, 0), jnp.complex64)
    b = jnp.asarray(_su3(1, 1)[0], jnp.complex64)
    codec_f, step_f = _steps("none")
    codec_c, step_c = _steps("two_row")
    out_f = codec_f.unpack(step_f(codec_f.pack(a), codec_f.pack_b(b)), _SITES)
    out_c = codec_c.unpack(step_c(codec_c.pack(a), codec_c.pack_b(b)), _SITES)
    err = float(jnp.max(jnp.abs(out_c - out_f)))
    assert err < 1e-5, err
    # the STORED rows (0, 1) are the same FMA chain in both kernels — they
    # agree to ~ulp even off the group manifold (checked below)


def test_compressed_multiply_stored_rows_track_full_kernel_any_input():
    """Rows 0/1 of the compressed product never involve reconstruction on
    the OUTPUT side: for arbitrary (non-unitary) input they match the full
    kernel's rows 0/1 at f32 rounding — the compressed multiply's stored
    output is as exact as the full layout's."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((_SITES, 4, 3, 3))
                    + 1j * rng.standard_normal((_SITES, 4, 3, 3)),
                    jnp.complex64)
    b = jnp.asarray(rng.standard_normal((4, 3, 3))
                    + 1j * rng.standard_normal((4, 3, 3)), jnp.complex64)
    codec_f, step_f = _steps("none")
    codec_c, step_c = _steps("two_row")
    full_p = codec_f.planar_view(step_f(codec_f.pack(a), codec_f.pack_b(b)))
    comp_p = codec_c.planar_view(step_c(codec_c.pack(a), codec_c.pack_b(b)))
    rows = list(layouts.COMP_ROW_INDICES)
    scale = float(jnp.max(jnp.abs(full_p)))
    err = float(jnp.max(jnp.abs(comp_p - full_p[:, rows, :])))
    assert err <= 4e-6 * max(scale, 1.0), (err, scale)


@pytest.fixture(scope="module")
def two_row_service():
    """One two-row service, so its compiled codec serves every k."""
    from repro.serve.su3 import ServiceConfig, SU3Service

    return SU3Service(ServiceConfig(autotune=False, tile=16,
                                    compression="two_row"))


@pytest.mark.parametrize("k", [1, 2, 4])
def test_compressed_service_multiply_matches_full_plan(two_row_service, k):
    """A two-row served multiply of SU(3) data, its codec on the device,
    agrees with K chained multiplies of the FULL-width plan within the
    plan's verification tolerance."""
    a = jnp.asarray(_su3(16, 3 + k), jnp.complex64)
    b = jnp.asarray(_su3(1, 4 + k)[0], jnp.complex64)
    rid = two_row_service.submit(a, b, k=k)
    two_row_service.run_until_drained()
    full = build_plan(EngineConfig(L=2, tile=16))
    c_phys = full.fused_step(k)(full.codec.pack(a), full.codec.pack_b(b))
    err = float(jnp.max(jnp.abs(two_row_service.pop_result(rid)
                                - full.unpack(c_phys, 16))))
    assert err < verify_tolerance("float32", reconstruct=True), (k, err)


@pytest.mark.parametrize("dtype,accum", [("float32", ""),
                                         ("bfloat16", "float32")])
def test_compressed_engine_run_verifies_and_streams_two_thirds(dtype, accum):
    rows = {}
    for compression in ("none", "two_row"):
        cfg = EngineConfig(L=4, tile=64, dtype=dtype, accum_dtype=accum,
                           iterations=1, warmups=0, compression=compression)
        r = SU3Engine(cfg).run()
        assert r.verified, compression
        rows[compression] = r.row()
    assert rows["two_row"]["compression"] == "two_row"
    # 96 words/site vs 144: the whole tentpole in one ratio
    assert (rows["two_row"]["bytes_per_site"] * 3
            == rows["none"]["bytes_per_site"] * 2)


@pytest.mark.parametrize("compression", ["none", "two_row"])
def test_stencil_depth2_single_host_bit_identical(compression):
    """ONE depth-2 application == TWO depth-1 applications, bitwise — the
    single-host fast check of the communication-avoiding schedule (the
    1/2/4-host forced-device version runs in benchmarks/stencil.py and is
    gated by scripts/bench_diff.py)."""
    cfg = EngineConfig(L=4, tile=64, iterations=1, warmups=0,
                      compression=compression)
    plan = build_plan(cfg)
    u, v = plan.init_stencil_data()
    s1 = plan.stencil_step(overlap=False, depth=1)
    s2 = plan.stencil_step(overlap=False, depth=2)
    out1 = s1(u, v)
    assert plan.verify_stencil(out1), "depth-1 fixed point"
    assert bool(jnp.array_equal(s2(u, v), s1(u, out1)))


def test_compressed_stencil_bf16_storage_verifies():
    cfg = EngineConfig(L=4, tile=64, dtype="bfloat16", accum_dtype="float32",
                      iterations=1, warmups=0, compression="two_row")
    plan = build_plan(cfg)
    u, v = plan.init_stencil_data()
    out = plan.stencil_step(overlap=False)(u, v)
    assert plan.verify_stencil(out)
