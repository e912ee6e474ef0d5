"""MILC's even/odd staggered operator against its plain reference.

Small lattices on seeded hot fields (an independent random SU(3) link per
site and direction): the kernel's half-lattice Dslash against
``staggered_apply_reference``, D's anti-Hermiticity, the normal operator's
Hermiticity and positivity, where the phases and the antiperiodic boundary
flip, solves through ``cg_solve`` and ``SU3Service.submit_solve`` against
``staggered_eo_reference_solve``, the same solve on four devices, and the
site-local-adjoint stencil and CG left bit for bit as they were.
"""
import functools
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.su3 import layouts
from repro.core.su3.plan import (
    CGMaxItersError,
    EngineConfig,
    StaggeredField,
    build_plan,
    gather_neighbors,
    half_neighbors,
    staggered_apply_reference,
    staggered_eo_reference_solve,
    staggered_neighbor_tables,
    staggered_phases,
    verify_tolerance,
)
from repro.serve.su3 import ServiceConfig, SU3Service

MASS = 0.635


def _su3(rng, n):
    z = rng.normal(size=(n, 3, 3)) + 1j * rng.normal(size=(n, 3, 3))
    q, r = np.linalg.qr(z)
    d = np.diagonal(r, axis1=-2, axis2=-1)
    q = q * (d / np.abs(d))[..., None, :]
    return (q / np.linalg.det(q)[..., None, None] ** (1 / 3)).astype(np.complex64)


def _vec(rng, n):
    return (rng.normal(size=(n, 3)) + 1j * rng.normal(size=(n, 3))).astype(np.complex64)


def _hot(L, seed=15):
    rng = np.random.default_rng(seed)
    return _su3(rng, L**4 * 4).reshape(L**4, 4, 3, 3), _vec(rng, L**4)


def _plan(L, **kw):
    return build_plan(EngineConfig(L=L, tile=128, **kw))


def _dslash(plan, field, v, L):
    """D v on the whole lattice through the kernel's two half passes."""
    even, odd = layouts.parity_sites(L)
    out = np.zeros((L**4, 3), np.complex64)
    out[even] = plan.unpack_half(plan.dslash_half(field, plan.pack_half(v[odd]), 0))
    out[odd] = plan.unpack_half(plan.dslash_half(field, plan.pack_half(v[even]), 1))
    return out


def _rel(x, ref):
    x, ref = np.asarray(x, np.complex128), np.asarray(ref, np.complex128)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("L, dtype, accum", [
    (4, "float32", ""), (6, "float32", ""), (4, "bfloat16", "float32")])
def test_half_lattice_dslash_matches_reference(L, dtype, accum):
    u, v = _hot(L)
    plan = _plan(L, dtype=dtype, accum_dtype=accum)
    got = _dslash(plan, plan.prepare_staggered(u, MASS), v, L)
    ref = staggered_apply_reference(jnp.asarray(u), jnp.asarray(v), L)
    # f32: a few ulps of the 8-term sums; bf16 storage rounds links and
    # vectors to 8 bits (verify_tolerance's rule for stored bf16)
    tol = verify_tolerance(dtype, accum) if dtype == "bfloat16" else 1e-6
    assert _rel(got, ref) < tol


def test_dslash_is_anti_hermitian():
    L = 4
    u, v = _hot(L)
    w = _vec(np.random.default_rng(3), L**4)
    plan = _plan(L)
    field = plan.prepare_staggered(u, MASS)
    lhs = np.vdot(w.astype(np.complex128), _dslash(plan, field, v, L))
    rhs = -np.vdot(_dslash(plan, field, w, L).astype(np.complex128), v)
    assert abs(lhs - rhs) < 1e-5 * abs(lhs)


def _normal_apply(plan, field, x_p):
    """(4 m^2 - D_eo D_oe) x through the CG's own apply (p' = x at beta 0)."""
    h = plan._cg_helpers()
    zeros = jnp.zeros_like(x_p)
    _p, ap = plan._ks_apply()(field, x_p, zeros, h["coef"](0.0, field.sigma))
    return plan.unpack_half(ap)


def test_normal_operator_is_hermitian_and_positive():
    L = 4
    u, _ = _hot(L)
    rng = np.random.default_rng(5)
    x, y = _vec(rng, L**4 // 2), _vec(rng, L**4 // 2)
    plan = _plan(L)
    field = plan.prepare_staggered(u, MASS)
    ax = np.asarray(_normal_apply(plan, field, plan.pack_half(x)), np.complex128)
    ay = np.asarray(_normal_apply(plan, field, plan.pack_half(y)), np.complex128)
    x, y = x.astype(np.complex128), y.astype(np.complex128)
    assert abs(np.vdot(y, ax) - np.conj(np.vdot(x, ay))) < 1e-5 * abs(np.vdot(y, ax))
    # -D_eo D_oe = D_oe^dag D_oe >= 0, so <x, A x> >= 4 m^2 |x|^2, real
    xax = np.vdot(x, ax)
    assert abs(xax.imag) < 1e-5 * xax.real
    assert xax.real >= 4 * MASS**2 * np.vdot(x, x).real * (1 - 1e-6)


def test_phases_flip_where_they_should():
    L = 4
    eta = staggered_phases(L)

    def site(x, y, z, t):
        return ((t * L + z) * L + y) * L + x

    assert np.all(eta[0] == 1)
    assert eta[1, site(1, 0, 0, 0)] == -1 and eta[1, site(2, 3, 1, 0)] == 1
    assert eta[2, site(1, 1, 0, 0)] == 1 and eta[2, site(0, 1, 2, 1)] == -1
    assert eta[3, site(1, 1, 1, 0)] == -1 and eta[3, site(1, 1, 0, 2)] == 1
    # the t links of the last time slice carry the antiperiodic sign
    assert eta[3, site(0, 0, 0, L - 1)] == -1 and eta[3, site(1, 0, 0, L - 1)] == 1

    # unit links: the prepared links are the signs themselves, the backward
    # ones those of the neighbour x - mu, negated
    unit = np.broadcast_to(np.eye(3, dtype=np.complex64), (L**4, 4, 3, 3))
    plan = _plan(L)
    field = plan.prepare_staggered(unit, MASS)
    even, _odd = layouts.parity_sites(L)
    fwd = np.asarray(field.fwd_e)[0, 0::9, : L**4 // 2]  # U[mu, 0, 0], real
    bwd = np.asarray(field.bwd_e)[0, 0::9, : L**4 // 2]
    back = even - 1 + np.where(even % L == 0, L, 0)  # x - x_hat
    assert np.array_equal(fwd, eta[:, even])
    assert np.array_equal(bwd[0], -eta[0, back])
    t_back = (even - L**3) % L**4  # x - t_hat, across the boundary at t = 0
    assert np.array_equal(bwd[3], -eta[3, t_back])


def test_t_boundary_is_antiperiodic():
    """A point source on the last time slice, unit links: the hop that
    crosses to t = 0 changes sign, the one that stays does not."""
    L = 4
    unit = np.broadcast_to(np.eye(3, dtype=np.complex64), (L**4, 4, 3, 3))
    src = ((L - 1) * L + 1) * L**2  # x = y = 0, z = 1, t = L - 1: even
    v = np.zeros((L**4, 3), np.complex64)
    v[src, 0] = 1.0
    plan = _plan(L)
    out = _dslash(plan, plan.prepare_staggered(unit, MASS), v, L)
    eta_t = -1.0  # (-1)^(x + y + z) at x = y = 0, z = 1, on every t
    # at t = 0 the source is x - t: -eta_t U^dag v(x - t), times the
    # boundary's -1 (a periodic t would read -eta_t)
    assert out[src - (L - 1) * L**3, 0] == pytest.approx(eta_t)
    # at t = L - 2 the source is x + t: eta_t U v(x + t), no boundary
    assert out[src - L**3, 0] == pytest.approx(eta_t)


def _reference(u, b, L):
    x, residuals, converged = staggered_eo_reference_solve(
        jnp.asarray(u), jnp.asarray(b), L, MASS, tol=1e-7)
    assert converged
    return np.asarray(x)


# The tolerance of a f32 solve to 1e-6 against the reference: the solution
# error is at most the condition number (about 41 at am = 0.635: (4 m^2 +
# ||D||^2) / 4 m^2 with ||D|| <= 8) times the relative residual, 4e-5 in the
# worst case and 1e-7 to 1e-6 as read; 1e-4 holds it with room.  bfloat16
# storage cannot reach that residual (its vectors round at 2^-8), and its
# best iterate lies 1e-3 to 1e-2 off.
SOLVE_TOL = 1e-4


def test_cg_solve_matches_reference():
    L = 4
    u, v = _hot(L)
    b = v[layouts.parity_sites(L)[0]]
    plan = _plan(L)
    field = plan.prepare_staggered(u, MASS)
    assert isinstance(field, StaggeredField) and field.sigma == pytest.approx(4 * MASS**2)
    res = plan.cg_solve(field, plan.pack_half(b), tol=1e-6, max_iters=200)
    assert res.converged and 28 <= res.iterations <= 34
    # two half passes an iteration, the lagged check's extra one included
    assert res.dslash_applies == 2 * (res.iterations + 1)
    assert _rel(plan.unpack_half(res.x_p), _reference(u, b, L)) < SOLVE_TOL


def test_bf16_stored_solve_fails_the_tolerance():
    L = 4
    u, v = _hot(L)
    b = v[layouts.parity_sites(L)[0]]
    plan = _plan(L, dtype="bfloat16", accum_dtype="float32")
    field = plan.prepare_staggered(u, MASS)
    try:
        x_p = plan.cg_solve(field, plan.pack_half(b), tol=1e-6, max_iters=60).x_p
    except CGMaxItersError as err:
        x_p = err.result.x_p
    assert _rel(plan.unpack_half(x_p), _reference(u, b, L)) > SOLVE_TOL


def test_served_solve_matches_reference():
    L = 4
    u, v = _hot(L)
    b = v[layouts.parity_sites(L)[0]]
    svc = SU3Service(ServiceConfig(autotune=False, tile=128))
    rid = svc.submit_solve(u, b, tol=1e-6, max_iters=200, mass=MASS)
    svc.run_until_drained()
    x = np.asarray(svc.pop_result(rid))
    assert x.shape == (L**4 // 2, 3)
    assert _rel(x, _reference(u, b, L)) < SOLVE_TOL
    assert ("solve", L, "ks_eo", MASS) in svc._seen_shapes


@pytest.mark.parametrize("bad", [
    dict(mass=0.0), dict(mass=-0.1), dict(shape=(4**4, 3))])
def test_submit_solve_refuses_a_bad_staggered_request(bad):
    u, v = _hot(4)
    b = v if "shape" in bad else v[: 4**4 // 2]
    svc = SU3Service(ServiceConfig(autotune=False, tile=128))
    with pytest.raises(ValueError):
        svc.submit_solve(u, b, mass=bad.get("mass", MASS))


def test_staggered_refuses_what_it_cannot_run():
    u, _ = _hot(4)
    with pytest.raises(ValueError, match="full link rows"):
        _plan(4, compression="two_row").prepare_staggered(u, MASS)
    with pytest.raises(ValueError, match="even L"):
        layouts.parity_sites(3)
    plan = _plan(4)
    b_p = plan.pack_half(np.ones((4**4 // 2, 3), np.complex64))
    with pytest.raises(ValueError, match="pass no sigma"):
        plan.cg_solve(plan.prepare_staggered(u, MASS), b_p, sigma=16.0)


def test_kernel_with_its_own_backward_links_equal_to_u_is_the_site_local_stencil():
    from repro.kernels import ops

    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.normal(size=(2, 36, 256)).astype(np.float32))
    nbr = jnp.asarray(rng.normal(size=(8, 2, 3, 256)).astype(np.float32))
    a = ops.su3_stencil_planar(u, nbr, tile=128)
    b = ops.su3_stencil_planar(u, nbr, u, tile=128)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("L, half", [(4, 128), (4, 192), (6, 648), (6, 704)])
@pytest.mark.parametrize("parity", [0, 1])
def test_rolled_half_lattice_neighbors_equal_the_table_gather(L, half, parity):
    """The half-lattice operand built from rolls reads, for the sites of
    each parity, the values the gather through
    ``staggered_neighbor_tables`` reads: on L**4 // 2 sites and past it,
    where padding sites read themselves."""
    rng = np.random.default_rng(100 * L + half + parity)
    v_p = jnp.asarray(rng.standard_normal((2, 3, half)).astype(np.float32))
    table = staggered_neighbor_tables(L, half)[parity]
    rolled = jax.jit(lambda v: half_neighbors(v, L, parity))(v_p)
    gathered = jax.jit(lambda v: gather_neighbors(v, table))(v_p)
    assert rolled.shape == (8, 2, 3, half)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(gathered))


def _site_tables(hlo):
    """The integer and boolean array constants of a compiled program: a
    neighbour table or a per-site mask, had one been closed over."""
    return re.findall(r"(?:s32|s64|u32|pred)\[\d[\d,]*\]\S* constant\(", hlo)


def test_cg_apply_and_dslash_programs_hold_no_gather_and_no_table():
    """The site-local fused CG apply and both half-lattice Dslash passes
    form their neighbours from rolls: no gather op, and no ``s32[8,...]``
    table or any per-site integer or boolean constant in the program."""
    L = 4
    u, v = _hot(L)
    plan = _plan(L)
    v_p = plan.pack_rhs(v)
    coefs = jnp.zeros((1, 2), jnp.float32)
    programs = [jax.jit(plan._cg_apply(fused=True, overlap=False)).lower(
        plan.pack_gauge(u), v_p, v_p, coefs)]
    field = plan.prepare_staggered(u, MASS)
    v_half = plan.pack_half(v[: L**4 // 2])
    programs += [jax.jit(functools.partial(plan.dslash_half, parity=p)).lower(field, v_half)
                 for p in (0, 1)]
    for lowered in programs:
        hlo = lowered.compile().as_text()
        assert " gather(" not in hlo
        assert "s32[8," not in hlo
        assert _site_tables(hlo) == []


# sha256 of the site-local stencil's and CG's outputs on these fixtures,
# computed with the tree before the staggered operator was added: the
# stencil kernel's new operand leaves the site-local programs as they were.
SITE_LOCAL_DIGESTS = {
    "stencil.f32": "2d9b4c0a3b399ffa8913fb65634e6b86a5c5dfa7b4e0a2b5e067edbc3d4caf05",
    "cg.f32.True": "985d41f78406b806da20f23c69c848382d902e5cf42043557b157243eca5b57e",
    "cg.f32.True.residuals": "19c0427919b7d7b1489591ae7a340fd7ec5063a5def41caa3c318e5aa12ee795",
    "cg.f32.False": "985d41f78406b806da20f23c69c848382d902e5cf42043557b157243eca5b57e",
    "cg.f32.False.residuals": "19c0427919b7d7b1489591ae7a340fd7ec5063a5def41caa3c318e5aa12ee795",
    "stencil.bf16": "6ff4848fbe3b0c2a353e5f1faf17375727b5da79f45ba286cc9a95a1acf64785",
    "cg.bf16.True": "be6666dad1f0938cbce2d768c81dfb09f7b44c5a0704d7f2faa6d39993566f7c",
    "cg.bf16.True.residuals": "cd9f3ed9a5db20e3bd2b67033cec90b2e6a0c2550dc29e7b7ca4bde2385d5d29",
    "cg.bf16.False": "4e2f5fdfc19fddf66f446facc51656fd5e5319718ffe34b7fe37cbb132ccddb7",
    "cg.bf16.False.residuals": "e1df8556c83190874d4818695c562ac0cc7536f0782b898258fd93b020d537ae",
    "stencil.two_row": "1b7954249bf0951ffb2a26e5d3d201def366be72832e7eb23de9081bc895b621",
    "cg.two_row.True": "5ea002cc3e712545f0d05cba6f8169bf2a41a9add5577578e70cc68ad4b19068",
    "cg.two_row.True.residuals": "c8406ec10960618ccf320b211fc5afe792e17a72aa21fba84d2580366855f785",
    "cg.two_row.False": "5ea002cc3e712545f0d05cba6f8169bf2a41a9add5577578e70cc68ad4b19068",
    "cg.two_row.False.residuals": "c8406ec10960618ccf320b211fc5afe792e17a72aa21fba84d2580366855f785",
}


def _site_local_outputs():
    L, S = 4, 4**4
    rng = np.random.default_rng(1515)
    hot = _su3(rng, S * 4).reshape(S, 4, 3, 3)
    uniform = np.broadcast_to(_su3(rng, 4), (S, 4, 3, 3)).copy()
    v = (rng.normal(size=(S, 3)) + 1j * rng.normal(size=(S, 3))).astype(np.complex64)
    out = {}
    for name, cfg in (("f32", EngineConfig(L=L, tile=128)),
                      ("bf16", EngineConfig(L=L, tile=128, dtype="bfloat16",
                                            accum_dtype="float32")),
                      ("two_row", EngineConfig(L=L, tile=128, compression="two_row"))):
        plan = build_plan(cfg)
        v_p = plan.pack_rhs(v)
        s = plan.stencil_step(overlap=False)(plan.pack_gauge(hot), v_p)
        out[f"stencil.{name}"] = np.asarray(jax.device_get(s))
        u_phys = plan.pack_gauge(uniform)
        for fused in (True, False):
            res = plan.cg_solve(u_phys, v_p, tol=1e-5, max_iters=40, fused=fused)
            out[f"cg.{name}.{fused}"] = np.asarray(jax.device_get(res.x_p))
            out[f"cg.{name}.{fused}.residuals"] = np.asarray(res.residuals)
    return out


def test_site_local_stencil_and_cg_are_unchanged_bit_for_bit():
    got = {k: hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
           for k, a in _site_local_outputs().items()}
    assert got == SITE_LOCAL_DIGESTS


_FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.core.su3 import layouts
from repro.core.su3.plan import EngineConfig, build_plan

rng = np.random.default_rng(15)
L = 4
z = rng.normal(size=(L**4 * 4, 3, 3)) + 1j * rng.normal(size=(L**4 * 4, 3, 3))
q, r = np.linalg.qr(z)
d = np.diagonal(r, axis1=-2, axis2=-1)
q = q * (d / np.abs(d))[..., None, :]
u = (q / np.linalg.det(q)[..., None, None] ** (1 / 3)).astype(np.complex64)
u = u.reshape(L**4, 4, 3, 3)
b = (rng.normal(size=(L**4 // 2, 3)) + 1j * rng.normal(size=(L**4 // 2, 3))).astype(np.complex64)
out = {}
for n in (1, 4):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("sites",))
    plan = build_plan(EngineConfig(L=L, tile=32), mesh)
    field = plan.prepare_staggered(u, 0.635)
    res = plan.cg_solve(field, plan.pack_half(b), tol=1e-6, max_iters=200)
    devices = len(res.x_p.sharding.device_set)
    out[n] = (res.iterations, devices, np.asarray(plan.unpack_half(res.x_p)))
x1, x4 = out[1][2], out[4][2]
print(json.dumps({"iterations": [out[1][0], out[4][0]],
                  "devices": [out[1][1], out[4][1]],
                  "rel": float(np.abs(x4 - x1).max() / np.abs(x1).max())}))
"""


def test_staggered_solve_on_four_devices_matches_one(forced_subprocess_json):
    """The operator runs on the plan's site sharding (no overlap schedule):
    four forced CPU devices, each holding a quarter of every half lattice,
    reach the one-device answer to f32 rounding of the sharded reductions."""
    got = forced_subprocess_json(_FOUR_DEVICES)
    assert got["devices"] == [1, 4]
    assert abs(got["iterations"][0] - got["iterations"][1]) <= 1
    assert got["rel"] < 1e-5
