"""Overlap-scheduled Dslash stencil path.

Pins the PR's acceptance bars:

  * the overlapped ``ExecutionPlan.stencil_step`` is BIT-IDENTICAL to the
    non-overlapped reference on 1-host and (forced-device) multi-host
    meshes, for f32 and bf16-storage/f32-accumulate variants, across the
    SOA and AoSoA planar layouts;
  * the reference itself matches an independent canonical-complex oracle
    (periodic rolls on the (t, z, y, x) 4-D field);
  * ``HaloSpec`` interior/boundary/ghost ranges partition every shard
    exactly (disjoint + covering), including the single-host and
    ``n_shards > L`` slab-degeneracy edge cases;
  * the halo-charging stencil roofline rows carry halo bytes in the
    bandwidth term, and the pruned stencil sweep lands within 5% of its
    exhaustive sweep (same gate as test_autotune_pruning);
  * ``SU3Service`` serves stencil requests through the existing
    warm-pool/batching machinery, mixed with multiplies.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.su3 import plan as su3_plan
from repro.core.su3.layouts import Layout, make_codec
from repro.core.su3.plan import EngineConfig, build_plan, stencil_neighbor_tables
from repro.distributed.sharding import HaloSpec, VECTOR_WORDS_PER_SITE
from repro.kernels.su3_stencil import (
    STENCIL_FLOPS_PER_SITE,
    STENCIL_WORDS_PER_SITE,
    stencil_vmem_bytes,
)


def _rand_complex(rng, shape):
    r = rng.standard_normal(shape + (2,)).astype(np.float32)
    return jnp.asarray(r[..., 0] + 1j * r[..., 1], jnp.complex64)


def _pack_inputs(plan, a, v):
    S = a.shape[0]
    if plan.padded_sites > S:
        a = jnp.concatenate(
            [a, jnp.zeros((plan.padded_sites - S, 4, 3, 3), a.dtype)]
        )
    return plan.codec.pack(a), plan.codec.pack_vec(v, plan.padded_sites)


def _oracle(L, a, v):
    """Independent canonical stencil: periodic rolls on the 4-D field.

    out(x) = sum_mu U_mu(x) v(x+mu) + U_mu(x)^dag v(x-mu), with the t-major
    site linearization site = ((t*L + z)*L + y)*L + x.
    """
    S = L**4
    U = np.asarray(a).reshape(L, L, L, L, 4, 3, 3)  # (t, z, y, x, ...)
    V = np.asarray(v).reshape(L, L, L, L, 3)
    out = np.zeros((L, L, L, L, 3), np.complex64)
    axis_of_dir = {0: 3, 1: 2, 2: 1, 3: 0}  # x, y, z, t
    for d in range(4):
        ax = axis_of_dir[d]
        vf = np.roll(V, -1, axis=ax)
        vb = np.roll(V, +1, axis=ax)
        out += np.einsum("...kl,...l->...k", U[..., d, :, :], vf)
        out += np.einsum("...lk,...l->...k", U[..., d, :, :].conj(), vb)
    return out.reshape(S, 3)


# -- reference correctness vs oracle ------------------------------------------


@pytest.mark.parametrize("L,tile", [(2, 8), (4, 64)])
def test_reference_matches_canonical_oracle(L, tile):
    rng = np.random.default_rng(L)
    S = L**4
    p = build_plan(EngineConfig(L=L, tile=tile, iterations=1, warmups=0))
    a, v = _rand_complex(rng, (S, 4, 3, 3)), _rand_complex(rng, (S, 3))
    u_phys, v_p = _pack_inputs(p, a, v)
    got = np.asarray(p.unpack_vec(p.stencil_reference_step()(u_phys, v_p)))
    want = _oracle(L, a, v)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_fixed_point_verification_and_constants():
    p = build_plan(EngineConfig(L=4, tile=64, iterations=1, warmups=0))
    u, v = p.init_stencil_data()
    out = p.stencil_step(overlap=False)(u, v)
    assert p.verify_stencil(out)
    assert STENCIL_FLOPS_PER_SITE == 576
    assert STENCIL_WORDS_PER_SITE == 126
    assert stencil_vmem_bytes(64) == 126 * 64 * 4
    # padded plans (tile > L**4) stay correct: pad sites self-neighbor
    p_pad = build_plan(EngineConfig(L=2, tile=128, iterations=1, warmups=0))
    assert p_pad.padded_sites > 16
    u, v = p_pad.init_stencil_data()
    assert p_pad.verify_stencil(p_pad.stencil_step(overlap=False)(u, v))


# -- bit-identity: overlap vs reference, single host --------------------------


@pytest.mark.parametrize("layout", [Layout.SOA, Layout.AOSOA])
@pytest.mark.parametrize("dtype,accum", [("float32", ""), ("bfloat16", "float32")])
def test_overlap_bit_identical_single_host(layout, dtype, accum):
    rng = np.random.default_rng(11)
    L, S = 4, 256
    p = build_plan(EngineConfig(
        L=L, tile=64, layout=layout, dtype=dtype, accum_dtype=accum,
        iterations=1, warmups=0,
    ))
    a, v = _rand_complex(rng, (S, 4, 3, 3)), _rand_complex(rng, (S, 3))
    u_phys, v_p = _pack_inputs(p, a, v)
    ref = p.stencil_step(overlap=False)(u_phys, v_p)
    ovl = p.stencil_step(overlap=True)(u_phys, v_p)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ovl))
    # default schedule on a single-host mesh is the reference
    assert p.stencil_step() is p.stencil_step(overlap=False)


# -- bit-identity: multi-host (forced devices, subprocess) --------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.core.su3.plan import EngineConfig, build_plan
from repro.core.su3.layouts import Layout
from repro.launch.mesh import MeshSpec

rng = np.random.default_rng(5)
def rand_c(shape):
    r = rng.standard_normal(shape + (2,)).astype(np.float32)
    return jnp.asarray(r[..., 0] + 1j * r[..., 1], jnp.complex64)

checked = []
for layout, dtype, accum in (
    ("soa", "float32", ""),
    ("aosoa", "float32", ""),
    ("soa", "bfloat16", "float32"),
    ("aosoa", "bfloat16", "float32"),
):
    L, S = 4, 256
    a, v = rand_c((S, 4, 3, 3)), rand_c((S, 3))
    cfg = EngineConfig(L=L, tile=32, layout=Layout(layout), dtype=dtype,
                       accum_dtype=accum, iterations=1, warmups=0)
    p1 = build_plan(cfg)  # 1-D mesh over 4 devices
    p2 = build_plan(cfg, MeshSpec(hosts=2, devices_per_host=2))
    p4 = build_plan(cfg, MeshSpec(hosts=4, devices_per_host=1))  # slab == face
    assert p2.is_multi_host and p2.stencil_step() is p2.stencil_step(overlap=True)
    # one slab rolls under either schedule; slabs gather their local wrap
    assert p1._neighbor_access(True) == "roll" == p2._neighbor_access(False)
    assert p2._neighbor_access(True) == "gather" == p4._neighbor_access(True)
    outs = []
    for p in (p1, p2, p4):
        u_phys = p.codec.pack(a)
        v_p = p.codec.pack_vec(v, p.padded_sites)
        ref = p.stencil_step(overlap=False)(u_phys, v_p)
        ovl = p.stencil_step(overlap=True)(u_phys, v_p)
        r, o = (np.asarray(jax.device_get(x)) for x in (ref, ovl))
        assert np.array_equal(r, o), (layout, dtype, p.n_hosts)
        outs.append(r.astype(np.float32))
    # same values on every mesh (the multi-host schedules vs single-host)
    assert np.array_equal(outs[0], outs[1]) and np.array_equal(outs[0], outs[2])
    checked.append([layout, dtype, accum])
print(json.dumps(checked))
"""


def test_overlap_bit_identical_multi_host_subprocess(forced_subprocess_json):
    """Forced host-platform devices lock at first jax init, so the 2- and
    4-host (slab-degenerate) meshes run in a subprocess — the shared
    conftest runner."""
    checked = forced_subprocess_json(_SUBPROC)
    assert len(checked) == 4  # 2 layouts x 2 dtype variants


# -- neighbor tables ----------------------------------------------------------


def test_neighbor_tables_local_equals_global_on_interior():
    L, H = 4, 2
    glob, local, bidx = stencil_neighbor_tables(L, L**4, H)
    spec = HaloSpec(L=L, n_shards=H)
    interior = np.concatenate([
        np.arange(a, b) for s in range(H) for (a, b) in spec.interior_ranges(s)
    ] or [np.empty(0, np.int64)]).astype(np.int64)
    boundary = np.concatenate([
        np.arange(a, b) for s in range(H) for (a, b) in spec.boundary_ranges(s)
    ]).astype(np.int64)
    np.testing.assert_array_equal(np.sort(bidx), np.sort(boundary))
    np.testing.assert_array_equal(glob[:, interior], local[:, interior])
    # x/y/z directions are slab-local everywhere
    for d in (0, 1, 2, 4, 5, 6):
        np.testing.assert_array_equal(glob[d], local[d])
    # +-t differ exactly on the boundary sites
    diff = np.where((glob[3] != local[3]) | (glob[7] != local[7]))[0]
    np.testing.assert_array_equal(np.sort(diff), np.sort(boundary))
    # padding sites self-neighbor
    glob_p, local_p, _ = stencil_neighbor_tables(2, 64, 1)
    np.testing.assert_array_equal(glob_p[:, 16:], np.tile(np.arange(16, 64), (8, 1)))


@pytest.mark.parametrize("L, tile", [(4, 128), (6, 16), (6, 128)])
def test_rolled_neighbors_equal_the_periodic_gather(L, tile):
    """The plan's neighbour operand for the exact periodic table is built
    from rolls, and reads the very values the gather through that table
    reads, in all 8 directions; at L=6 with tile 128 the plan pads its
    sites past L**4, and the padding sites read themselves."""
    plan = build_plan(EngineConfig(L=L, tile=tile))
    n = plan.padded_sites
    assert (n > L**4) == (tile == 128 and L == 6)
    glob = plan._stencil_geometry()[0]
    # one slab: with or without the overlap schedule the neighbours roll
    assert plan._neighbor_access(overlap=False) == "roll"
    assert plan._neighbor_access(overlap=True) == "roll"
    rng = np.random.default_rng(L * tile)
    v_p = jnp.asarray(rng.standard_normal((2, 3, n)).astype(np.float32))
    rolled = jax.jit(lambda v: plan._neighbor_operand(v, overlap=False))(v_p)
    gathered = jax.jit(lambda v: su3_plan.gather_neighbors(v, glob))(v_p)
    assert rolled.shape == (8, 2, 3, n)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(gathered))


# -- HaloSpec edge cases (satellite) ------------------------------------------


def test_halo_ranges_single_host_no_boundary():
    h = HaloSpec(L=4, n_shards=1)
    assert h.boundary_ranges(0) == [] and h.ghost_ranges(0) == []
    assert h.interior_ranges(0) == [(0, 256)]
    assert h.boundary_sites == 0


@pytest.mark.parametrize("L,n_shards", [
    (4, 2),   # regular two-slab split
    (4, 4),   # slab thickness == one face: all boundary, no interior
    (4, 8),   # n_shards > L: sub-face slab degeneracy
    (4, 16),  # extreme degeneracy
    (2, 2),
])
def test_halo_ranges_partition_exactly(L, n_shards):
    spec = HaloSpec(L=L, n_shards=n_shards)
    for s in range(n_shards):
        lo, hi = spec.shard_range(s)
        ranges = spec.interior_ranges(s) + spec.boundary_ranges(s)
        sites = sorted(x for a, b in ranges for x in range(a, b))
        assert sites == list(range(lo, hi)), (L, n_shards, s)  # disjoint+cover
        for a, b in spec.ghost_ranges(s):
            assert b > a
            assert not (a >= lo and b <= hi), "ghosts must be remote"


def test_halo_degenerate_slab_counts():
    hd = HaloSpec(L=4, n_shards=8)  # per-shard 32 < face 64
    assert hd.sites_per_shard == 32
    assert hd.boundary_sites == 32  # capped at the slab, not 2*face
    assert hd.interior_fraction == 0.0
    assert hd.interior_ranges(0) == []


def test_halo_spec_dtype_and_vector_words():
    from repro.distributed import sharding
    from repro.launch.mesh import MeshSpec
    mesh = MeshSpec(hosts=2, devices_per_host=1).resolve([jax.devices()[0]] * 2)
    assert sharding.halo_spec(4, mesh, dtype="bfloat16").word_bytes == 2
    assert sharding.halo_spec(4, mesh).word_bytes == 4
    h = sharding.halo_spec(4, mesh, words_per_site=VECTOR_WORDS_PER_SITE)
    assert h.halo_bytes_per_exchange == 128 * 6 * 4
    with pytest.raises(ValueError, match="contradicts"):
        sharding.halo_spec(4, mesh, 4, dtype="bfloat16")
    # the plan's stencil halo prices vector words at storage width
    p = build_plan(EngineConfig(L=4, tile=64, dtype="bfloat16",
                                accum_dtype="float32"))
    sh = p.stencil_halo()
    assert sh.words_per_site == 6 and sh.word_bytes == 2


# -- stencil roofline + pruned sweep (same gate as test_autotune_pruning) -----


def test_predict_stencil_charges_halo_in_bandwidth_term():
    c = autotune.StencilCandidate(tile=64, overlap=False)
    p1 = autotune.predict_stencil(c, L=4, hosts=1)
    p2 = autotune.predict_stencil(c, L=4, hosts=2)
    assert p1["halo_s"] == 0.0 and p1["halo_bytes_per_exchange"] == 0
    # vector halo: boundary sites x 6 words x 4 B
    assert p2["halo_bytes_per_exchange"] == 128 * 6 * 4
    stream = 256 * STENCIL_WORDS_PER_SITE * 4
    assert p2["bandwidth_bytes"] == stream + p2["halo_bytes_per_exchange"]
    # all shards run concurrently: the bound composes the PER-SHARD core
    # (core / hosts) with the per-shard halo; serial pays the halo on top
    core = max(p2["compute_s"], p2["memory_s"], p2["issue_s"])
    assert p2["core_shard_s"] == pytest.approx(core / 2)
    assert p2["bound_s"] == pytest.approx(p2["core_shard_s"] + p2["halo_s"])
    # overlapped schedule hides it under the core bound (plus recompute)
    po = autotune.predict_stencil(
        autotune.StencilCandidate(tile=64, overlap=True), L=4, hosts=2)
    assert po["bound_s"] == pytest.approx(
        max(po["core_shard_s"], po["halo_s"])
        + po["boundary_fraction"] * po["core_shard_s"])
    # hosts=1 predicts IDENTICAL schedules; the persisted flag must then be
    # the deterministic serial preference, not measured jitter
    cfgs = [autotune.predict_stencil(
        autotune.StencilCandidate(tile=64, overlap=ov), L=4, hosts=1)
        for ov in (False, True)]
    assert cfgs[0]["bound_s"] == cfgs[1]["bound_s"]


def test_stencil_enumeration_gates_on_vmem():
    # 262144-site tile: 126 words/site x 4 B ~= 126 MiB > 16 MiB VMEM -> out
    cands = autotune.enumerate_stencil_candidates(tiles=(128, 262144))
    assert {c.tile for c in cands} == {128}
    assert {c.overlap for c in cands} == {False, True}
    # a wider accumulate re-inflates the resident set past VMEM
    big = autotune.enumerate_stencil_candidates(tiles=(32768,), overlaps=(False,))
    none = autotune.enumerate_stencil_candidates(
        tiles=(32768,), overlaps=(False,), dtype="float32", accum_dtype="float64")
    assert len(big) == 1 and len(none) == 0


def test_stencil_pruned_sweep_within_5pct_of_exhaustive(monkeypatch):
    """The PR's acceptance bar, stencil edition: measure <= 50% of the
    (tile, overlap) grid; the selected variant's measured GFLOPS within 5%
    of the exhaustive sweep's best."""
    monkeypatch.setattr(
        autotune, "stencil_instruction_model",
        lambda dtype="float32", accum_dtype="", tile=256: 500.0,
    )
    measured = []

    def deterministic_measure(cand):
        measured.append(cand)
        pred = autotune.predict_stencil(cand, L=4, hosts=2)["predicted_gflops"]
        wiggle = 1.0 + 0.03 * math.sin(
            7.0 * cand.tile + (13.0 if cand.overlap else 3.0))
        return {"tile": cand.tile, "overlap": cand.overlap, "vmem_kib": 1,
                "measured_gflops": pred * wiggle, "verified": True}

    exhaustive = autotune.stencil_sweep(
        L=4, hosts=2, prune=1.0, measure_fn=deterministic_measure)
    n_total = exhaustive["candidates_total"]
    assert exhaustive["candidates_measured"] == n_total
    best_exhaustive = max(r["measured_gflops"] for r in exhaustive["rows"])

    measured.clear()
    pruned = autotune.stencil_sweep(
        L=4, hosts=2, prune=0.5, measure_fn=deterministic_measure)
    assert len(measured) == pruned["candidates_measured"]
    assert pruned["candidates_measured"] <= math.ceil(0.5 * n_total)
    best_pruned = max(r["measured_gflops"] for r in pruned["rows"])
    assert best_pruned >= 0.95 * best_exhaustive
    for row in pruned["rows"]:
        assert {"halo_bytes_per_exchange", "bandwidth_bytes",
                "predicted_rank", "halo_s"} <= set(row)


def test_stencil_sweep_real_measurements_tiny_grid():
    # 2 tiles x the (overlap, depth) schedule grid {(F,1), (T,1), (T,2)}
    sweep = autotune.stencil_sweep(
        L=2, prune=0.5, tiles=(8, 16), overlaps=(False, True))
    assert sweep["candidates_total"] == 6
    assert sweep["candidates_measured"] == 3
    for row in sweep["rows"]:
        assert row["verified"], row
        assert row["measured_gflops"] > 0.0


def test_best_stencil_config_persists_and_caches(tmp_path, monkeypatch):
    monkeypatch.setattr(
        autotune, "stencil_instruction_model",
        lambda dtype="float32", accum_dtype="", tile=256: 500.0,
    )

    def stub(cand):
        return {"tile": cand.tile, "overlap": cand.overlap, "vmem_kib": 1,
                "measured_gflops": float(cand.tile + cand.overlap),
                "verified": True}

    cfg = autotune.best_stencil_config(
        L=4, hosts=2, cache_directory=str(tmp_path), measure_fn=stub)
    assert cfg["variant"] == "pallas_stencil" and not cfg["cached"]
    prov = cfg["stencil"]
    assert prov["hosts"] == 2
    assert prov["candidates_measured"] <= math.ceil(
        0.5 * prov["candidates_total"])
    again = autotune.best_stencil_config(
        L=4, hosts=2, cache_directory=str(tmp_path))
    assert again["cached"] and again["stencil"] == prov
    # the multiply cache validator never serves a stencil entry and vice versa
    assert autotune._valid_cache_hit({"config": cfg}) is None


# -- registry / plan wiring ---------------------------------------------------


def test_stencil_kernel_form_rejected_by_multiply_step():
    from repro.core.su3 import registry
    entry = registry.get_kernel("pallas_stencil")
    assert entry.form == registry.STENCIL
    codec = make_codec(Layout.SOA, tile=16)
    with pytest.raises(ValueError, match="stencil"):
        su3_plan.make_raw_step(codec, entry, tile=16)
    assert "pallas_stencil" in registry.kernel_names(form=registry.STENCIL)


def test_vec_codec_roundtrip():
    rng = np.random.default_rng(3)
    for dtype, tol in (("float32", 0.0), ("bfloat16", 1e-2)):
        codec = make_codec(Layout.SOA, tile=16, dtype=dtype)
        v = _rand_complex(rng, (20, 3))
        v_p = codec.pack_vec(v, 32)
        assert v_p.shape == (2, 3, 32)
        back = np.asarray(codec.unpack_vec(v_p, 20))
        if tol:
            np.testing.assert_allclose(back, np.asarray(v), atol=tol)
        else:
            np.testing.assert_array_equal(back, np.asarray(v))


# -- serving ------------------------------------------------------------------


def test_service_serves_stencil_requests_with_multiplies():
    from repro.kernels import ref as kref
    from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service

    rng = np.random.default_rng(9)
    svc = SU3Service(ServiceConfig(
        autotune=False, tile=16,
        batcher=BatcherConfig(max_batch=4, warm_batch_sizes=(1, 2, 4),
                              max_queue_depth=32),
    ))
    L, S = 2, 16
    us, vs, sids = [], [], []
    for _ in range(3):
        u, v = _rand_complex(rng, (S, 4, 3, 3)), _rand_complex(rng, (S, 3))
        us.append(u)
        vs.append(v)
        sids.append(svc.submit_stencil(u, v))
    am, bm = _rand_complex(rng, (S, 4, 3, 3)), _rand_complex(rng, (4, 3, 3))
    mid = svc.submit(am, bm, k=2)
    assert svc.run_until_drained() == 4

    # stencil results match the direct plan reference
    p = build_plan(EngineConfig(L=L, tile=16))
    ref_step = p.stencil_step(overlap=False)
    for u, v, rid in zip(us, vs, sids):
        u_phys, v_p = _pack_inputs(p, u, v)
        want = np.asarray(p.unpack_vec(ref_step(u_phys, v_p)))
        got = np.asarray(svc.pop_result(rid))
        np.testing.assert_allclose(got, want, atol=1e-5)
    # the multiply shared the pool and still completed correctly
    want_c = np.asarray(kref.su3_mult_ref(kref.su3_mult_ref(am, bm), bm))
    np.testing.assert_allclose(np.asarray(svc.pop_result(mid)), want_c, atol=1e-4)
    # one warm runner served both request kinds
    assert len(svc.pool_keys()) == 1


def test_service_stencil_validates_vector_shape():
    from repro.serve.su3 import ServiceConfig, SU3Service
    svc = SU3Service(ServiceConfig(autotune=False, tile=16))
    rng = np.random.default_rng(1)
    u = _rand_complex(rng, (16, 4, 3, 3))
    with pytest.raises(ValueError, match="vector field"):
        svc.submit_stencil(u, _rand_complex(rng, (8, 3)))


def test_service_stencil_stream_does_not_starve_chains():
    """Kind fairness: with BOTH kinds pending, turns alternate — a sustained
    stencil stream must not starve a queued k=3 multiply."""
    from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service

    rng = np.random.default_rng(13)
    svc = SU3Service(ServiceConfig(
        autotune=False, tile=16,
        batcher=BatcherConfig(max_batch=2, warm_batch_sizes=(1, 2),
                              max_queue_depth=16),
    ))
    S = 16
    am, bm = _rand_complex(rng, (S, 4, 3, 3)), _rand_complex(rng, (4, 3, 3))
    mid = svc.submit(am, bm, k=3)
    u, v = _rand_complex(rng, (S, 4, 3, 3)), _rand_complex(rng, (S, 3))
    for step_n in range(12):
        if svc.has_result(mid):
            break
        svc.submit_stencil(u, v)  # keep the stencil queue non-empty
        svc.step()
    assert svc.has_result(mid), "multiply starved by stencil stream"
    from repro.kernels import ref as kref
    want = kref.su3_mult_ref(kref.su3_mult_ref(kref.su3_mult_ref(am, bm), bm), bm)
    np.testing.assert_allclose(np.asarray(svc.pop_result(mid)),
                               np.asarray(want), atol=1e-4)
    svc.run_until_drained()
