"""Chip smoke: the SU3 stack's main path, compiled on a TPU at the paper's L=32.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on a four-chip host

One chip runs, in one process and in this order: the paper's ``PAPER_L32``
plan (``SU3Engine`` plus fused chains), the default ``SU3Service`` with
autotune on (multiplies, a stencil and a CG solve at L=32), the autotuner's
stencil and CG sweeps, the megakernel service over a mixed L in {16, 32}
stream, and the bf16-storage and two-row plans.  ``--chips 4`` runs only the
plan sharded over four chips and the same plan on one chip beside it, and a
four-host service.

Every phase checks its results against a plain ``jax.numpy`` reference
(``kernels.ref.su3_mult_ref``, ``plan.stencil_apply_reference``,
``plan.cg_reference_solve``) within ``plan.verify_tolerance`` and prints one
JSON line: wall seconds, compile seconds, max error, and the device's
``peak_bytes_in_use``.  None of these is a device speed.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
a failure anywhere exits non-zero.  With no TPU it exits non-zero before
any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
AUTOTUNE_DIR = ROOT / ".su3_autotune"
SEED = 0

sys.path.insert(0, str(ROOT / "src"))
# both fail outside a checkout, before any phase
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.su3.layouts import on_host  # noqa: E402


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _device_check(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax found {devs[0].platform!r}); "
                 "this script only runs on the chip")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                 f"jax found {len(devs)}")
    print(json.dumps({
        "device_kind": devs[0].device_kind, "device_count": len(devs),
        "jax": jax.__version__, "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
    }), flush=True)
    return devs


class CompileClock:
    """Seconds JAX spends compiling (persistent-cache reads included) and
    persistent-cache hits, read per phase from JAX's monitoring events."""

    def __init__(self) -> None:
        from jax import monitoring

        self.seconds = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _random_su3(key, n: int):
    """``n`` random SU(3) matrices: Gram-Schmidt on complex normals, row 2 =
    conj(row0 x row1), so each is unitary with det 1 (what two-row gauge
    compression assumes)."""
    import jax
    import jax.numpy as jnp

    k0, k1 = jax.random.split(key)
    z = jax.lax.complex(jax.random.normal(k0, (n, 2, 3)),
                        jax.random.normal(k1, (n, 2, 3)))
    r0 = z[:, 0] / jnp.linalg.norm(z[:, 0], axis=-1, keepdims=True)
    r1 = z[:, 1] - jnp.sum(jnp.conj(r0) * z[:, 1], -1, keepdims=True) * r0
    r1 = r1 / jnp.linalg.norm(r1, axis=-1, keepdims=True)
    return jnp.stack([r0, r1, jnp.conj(jnp.cross(r0, r1))], axis=1)


# Canonical arrays and the plain references live on the host
# (``layouts.on_host``): a TPU pads their minor dimensions of 3 and 4 to 128
# lanes, so an L=32 canonical gauge field would not fit the chip.


def _lattice(key, L: int):
    """A random SU(3) gauge lattice ``(L**4, 4, 3, 3)`` complex64."""
    return on_host(lambda k: _random_su3(k, L**4 * 4).reshape(L**4, 4, 3, 3), key)


def _links(key):
    """A random SU(3) link set ``(4, 3, 3)`` — the multiply's B."""
    return on_host(lambda k: _random_su3(k, 4), key)


def _vector(key, L: int):
    """A random colour-vector field ``(L**4, 3)`` at scale 0.1."""
    import jax

    def make(key):
        k0, k1 = jax.random.split(key)
        shape = (L**4, 3)
        return 0.1 * jax.lax.complex(jax.random.normal(k0, shape),
                                     jax.random.normal(k1, shape))

    return on_host(make, key)


def _chain_ref(a, b, k: int):
    """``su3_mult_ref`` applied k times, on the host."""
    from repro.kernels.ref import su3_mult_ref

    def chain(a, b):
        for _ in range(k):
            a = su3_mult_ref(a, b)
        return a

    return on_host(chain, a, b)


def _host(x):
    """``x`` on the host: the arrays compared may sit on different chips."""
    import jax
    import numpy as np

    return np.asarray(jax.device_get(x))


def _err(x, y) -> float:
    import numpy as np

    return float(np.max(np.abs(_host(x) - _host(y))))


def _same(x, y) -> bool:
    import numpy as np

    return bool(np.array_equal(_host(x), _host(y)))


class Smoke:
    """Runs phases and prints one JSON line per phase."""

    def __init__(self, clock: CompileClock, device) -> None:
        self.clock = clock
        self.device = device

    def phase(self, name: str, fn, *args) -> None:
        gc.collect()
        c0, h0 = self.clock.seconds, self.clock.hits
        t0 = time.perf_counter()
        out = fn(*args)
        row = {"phase": name, "wall_s": time.perf_counter() - t0,
               "compile_s": self.clock.seconds - c0,
               "compile_cache_hits": self.clock.hits - h0}
        row.update(out)
        stats = self.device.memory_stats() or {}
        row["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        print(json.dumps(row, default=str), flush=True)
        failed = [k for k, (err, tol) in out["errors"].items() if not err <= tol]
        if failed:
            raise AssertionError(f"phase {name}: {failed} beyond tolerance")


# -- one chip ----------------------------------------------------------------


def phase_paper_plan(L: int, tile: int) -> dict:
    """PAPER_L32 through build_plan/SU3Engine, then fused chains that
    donate and alias their input, against su3_mult_ref applied k times."""
    import jax

    from repro.configs.su3_bench import PAPER_L32
    from repro.core.su3.engine import SU3Engine
    from repro.core.su3.plan import verify_tolerance

    cfg = dataclasses.replace(PAPER_L32, L=L, tile=tile, iterations=3)
    engine = SU3Engine(cfg)
    bench = engine.run()
    if not bench.verified:
        raise AssertionError("su3_bench fixed-point check failed")
    plan = engine.plan
    key = jax.random.PRNGKey(SEED)
    a = _lattice(key, L)
    b = _links(jax.random.fold_in(key, 1))
    a_phys, b_p = plan.pack_gauge(a), plan.pack_links(b)
    tol = verify_tolerance(cfg.dtype)
    errors = {"step": (_err(plan.unpack(plan.step(a_phys, b_p)),
                            _chain_ref(a, b, 1)), tol)}
    donated = {}
    for k in (4, 16):  # unrolled in-kernel chain, fori_loop chain
        x = plan.pack_gauge(a)
        c = plan.fused_step(k)(x, b_p)
        errors[f"fused{k}"] = (_err(plan.unpack(c), _chain_ref(a, b, k)), tol)
        donated[f"fused{k}"] = x.is_deleted()
    return {"errors": errors, "donated": donated, "plan": plan.describe()}


def phase_served(L: int) -> dict:
    """The default ServiceConfig (autotune on): multiplies, one stencil and
    one CG solve at L, each against its plain reference, plus the CPU-proven
    bit-identity contracts (fused = composed CG, overlap = reference)."""
    import jax

    from repro.core import autotune
    from repro.core.su3 import plan as su3_plan
    from repro.serve.su3 import ServiceConfig, SU3Service

    svc = SU3Service(ServiceConfig(cache_directory=str(AUTOTUNE_DIR)))
    key = jax.random.PRNGKey(SEED + 1)
    k = svc.default_k_for(L)
    mults = []
    for i in range(2):  # two requests: one L=32 batch of 8 does not fit
        a = _lattice(jax.random.fold_in(key, i), L)
        b = _links(jax.random.fold_in(key, 100 + i))
        mults.append((svc.submit(a, b, k=k), a, b))
    u = _lattice(jax.random.fold_in(key, 200), L)
    v = _vector(jax.random.fold_in(key, 201), L)
    sid = svc.submit_stencil(u, v)
    u_cg, b_cg = autotune._cg_measure_problem(L)  # per-direction constant
    cid = svc.submit_solve(u_cg, b_cg, tol=1e-6)
    svc.run_until_drained()

    plan = svc.runner_for(L).plan
    tol = su3_plan.verify_tolerance(plan.cfg.dtype)
    errors = {}
    for i, (rid, a, b) in enumerate(mults):
        errors[f"multiply{i}"] = (_err(svc.pop_result(rid),
                                       _chain_ref(a, b, k)), tol)
    out = svc.pop_result(sid)
    u_phys, v_p = plan.pack_gauge(u), plan.pack_rhs(v)
    ref_step = plan.stencil_reference_step()(u_phys, v_p)
    ref = on_host(lambda u, v: su3_plan.stencil_apply_reference(u, v, L), u, v)
    errors["stencil"] = (_err(out, ref), tol)
    errors["stencil_vs_plan_reference"] = (_err(out, plan.unpack_vec(ref_step)), tol)
    x = svc.pop_result(cid)
    x_ref, res_ref, conv_ref = on_host(
        lambda u, b: su3_plan.cg_reference_solve(u, b, L, tol=1e-6), u_cg, b_cg)
    if isinstance(x, Exception) or not conv_ref:
        raise AssertionError(f"solve failed: {x!r}, reference converged={conv_ref}")
    errors["solve"] = (_err(x, x_ref), tol)

    ug, bp = plan.pack_gauge(u_cg), plan.pack_rhs(b_cg)
    fused = plan.cg_solve(ug, bp, tol=1e-6, fused=True)
    composed = plan.cg_solve(ug, bp, tol=1e-6, fused=False)
    overlap = plan.stencil_step(overlap=True)(u_phys, v_p)
    return {
        "errors": errors, "fused_k": k, "tile": plan.cfg.tile,
        "cg_iterations": fused.iterations, "reference_iterations": len(res_ref),
        "fused_equals_composed_cg": _same(fused.x_p, composed.x_p)
        and fused.iterations == composed.iterations,
        "overlap_equals_reference_stencil": _same(overlap, ref_step),
        "metrics": {key: svc.metrics.snapshot()[key]
                    for key in ("completed", "dispatches", "compiles")},
    }


def phase_autotune(L: int) -> dict:
    """The stencil and CG sweeps complete on the chip (best_config already
    ran inside the served phase)."""
    from repro.core import autotune

    st = autotune.best_stencil_config(L=L, cache_directory=str(AUTOTUNE_DIR))
    cg = autotune.best_cg_config(L=L, cache_directory=str(AUTOTUNE_DIR))
    return {"errors": {}, "L": L,
            "stencil": {k: st[k] for k in ("tile", "overlap", "depth", "cached")},
            "cg": {k: cg[k] for k in ("tile", "fused", "cached")}}


def phase_megakernel(Ls: tuple[int, ...]) -> dict:
    """ServiceConfig(continuous=True, megakernel=True) over a mixed-L stream."""
    import jax

    from repro.core.su3.plan import verify_tolerance
    from repro.serve.su3 import ServiceConfig, SU3Service

    svc = SU3Service(ServiceConfig(continuous=True, megakernel=True,
                                   cache_directory=str(AUTOTUNE_DIR)))
    key = jax.random.PRNGKey(SEED + 2)
    reqs = []
    stream = [(Ls[0], 2), (Ls[1], 1), (Ls[0], 3), (Ls[1], 2)]
    for i, (L, k) in enumerate(stream):
        a = _lattice(jax.random.fold_in(key, i), L)
        b = _links(jax.random.fold_in(key, 100 + i))
        reqs.append((svc.submit(a, b, k=k), a, b, k, L))
    svc.run_until_drained()
    tol = verify_tolerance("float32")
    errors = {f"L{L}_k{k}_{i}": (_err(svc.pop_result(rid), _chain_ref(a, b, k)), tol)
              for i, (rid, a, b, k, L) in enumerate(reqs)}
    snap = svc.metrics.snapshot()
    return {"errors": errors,
            "dispatches_per_iteration": snap["dispatches_per_iteration"],
            "completed": snap["completed"]}


def phase_other_plans(L: int, tile: int) -> dict:
    """One served multiply on the bf16-storage/f32-accumulate plan (what
    brownout rung 2 swaps in) and one on the two-row plan."""
    import jax

    from repro.core.su3.plan import verify_tolerance
    from repro.serve.su3 import ServiceConfig, SU3Service

    key = jax.random.PRNGKey(SEED + 3)
    errors = {}
    for i, (name, kw) in enumerate((
            ("bf16_acc_f32", dict(dtype="bfloat16", accum_dtype="float32")),
            ("two_row", dict(compression="two_row")))):
        svc = SU3Service(ServiceConfig(autotune=False, tile=tile, **kw))
        a = _lattice(jax.random.fold_in(key, i), L)
        b = _links(jax.random.fold_in(key, 100 + i))
        rid = svc.submit(a, b, k=1)
        svc.run_until_drained()
        tol = verify_tolerance(kw.get("dtype", "float32"),
                               kw.get("accum_dtype", ""),
                               reconstruct="compression" in kw)
        errors[name] = (_err(svc.pop_result(rid), _chain_ref(a, b, 1)), tol)
    return {"errors": errors}


# -- four chips --------------------------------------------------------------


def phase_sharded_plan(L: int, tile: int) -> dict:
    """build_plan over MeshSpec(hosts=4) against the same plan on one chip:
    multiply, overlapped stencil at depth 1 and 2, and cg_solve."""
    import jax

    from repro.core import autotune
    from repro.core.su3 import plan as su3_plan
    from repro.core.su3.plan import EngineConfig, build_plan, make_site_mesh
    from repro.launch.mesh import MeshSpec

    cfg = EngineConfig(L=L, tile=tile)
    p4 = build_plan(cfg, MeshSpec(hosts=4, devices_per_host=1))
    p1 = build_plan(cfg, make_site_mesh(jax.devices()[:1]))
    tol = su3_plan.verify_tolerance(cfg.dtype)
    key = jax.random.PRNGKey(SEED + 4)
    a = _lattice(key, L)
    b = _links(jax.random.fold_in(key, 1))
    errors, identical, spans = {}, {}, {}

    c4 = p4.step(p4.pack_gauge(a), p4.pack_links(b))
    c1 = p1.step(p1.pack_gauge(a), p1.pack_links(b))
    spans["multiply"] = len(c4.sharding.device_set)
    errors["multiply"] = (_err(p4.unpack(c4), _chain_ref(a, b, 1)), tol)
    identical["multiply"] = _same(c4, c1)

    u = _lattice(jax.random.fold_in(key, 2), L)
    v = _vector(jax.random.fold_in(key, 3), L)
    u4, v4 = p4.pack_gauge(u), p4.pack_rhs(v)
    u1, v1 = p1.pack_gauge(u), p1.pack_rhs(v)
    ref1 = on_host(lambda u, v: su3_plan.stencil_apply_reference(u, v, L), u, v)
    ref2 = on_host(lambda u, v: su3_plan.stencil_apply_reference(u, v, L), u, ref1)
    one = p1.stencil_step(overlap=False)
    for depth, ref, single in ((1, ref1, one(u1, v1)), (2, ref2, one(u1, one(u1, v1)))):
        out = p4.stencil_step(overlap=True, depth=depth)(u4, v4)
        spans[f"stencil_d{depth}"] = len(out.sharding.device_set)
        errors[f"stencil_d{depth}"] = (_err(p4.unpack_vec(out), ref), tol)
        identical[f"stencil_d{depth}"] = _same(out, single)

    u_cg, b_cg = autotune._cg_measure_problem(L)
    r4 = p4.cg_solve(p4.pack_gauge(u_cg), p4.pack_rhs(b_cg))
    r1 = p1.cg_solve(p1.pack_gauge(u_cg), p1.pack_rhs(b_cg))
    x_ref, _res, conv = on_host(
        lambda u, b: su3_plan.cg_reference_solve(u, b, L, tol=1e-6), u_cg, b_cg)
    if not (r4.converged and r1.converged and conv):
        raise AssertionError("a CG solve did not converge")
    spans["cg"] = len(r4.x_p.sharding.device_set)
    errors["cg"] = (_err(p4.unpack_vec(r4.x_p), x_ref), tol)
    errors["cg_vs_one_chip"] = (_err(p4.unpack_vec(r4.x_p), p1.unpack_vec(r1.x_p)), tol)
    identical["cg"] = _same(r4.x_p, r1.x_p)
    if set(spans.values()) != {4}:
        raise AssertionError(f"sharded outputs do not span 4 devices: {spans}")
    return {"errors": errors, "devices_spanned": spans,
            "bit_identical_to_one_chip": identical,
            "cg_iterations": {"four": r4.iterations, "one": r1.iterations},
            "plan": p4.describe()}


def phase_four_host_service(Ls: tuple[int, ...], tile: int) -> dict:
    """SU3Service(hosts=4): one lattice size per host, each pool on its own
    chip (the oversubscription fallback must not fire)."""
    import jax

    from repro.core.su3.plan import verify_tolerance
    from repro.serve.su3 import ServiceConfig, SU3Service

    svc = SU3Service(ServiceConfig(hosts=4, autotune=False, tile=tile))
    key = jax.random.PRNGKey(SEED + 5)
    reqs = []
    for i, L in enumerate(Ls):
        a = _lattice(jax.random.fold_in(key, i), L)
        b = _links(jax.random.fold_in(key, 100 + i))
        reqs.append((svc.submit(a, b, k=2), a, b, L))
    svc.run_until_drained()
    tol = verify_tolerance("float32")
    errors = {f"L{L}": (_err(svc.pop_result(rid), _chain_ref(a, b, 2)), tol)
              for rid, a, b, L in reqs}
    chips = {key[0]: [str(d) for d in svc._pool[key].mesh.devices.flat]
             for key in svc.pool_keys()}
    distinct = {d for devs in chips.values() for d in devs}
    if sorted(chips) != [0, 1, 2, 3] or len(distinct) != 4:
        raise AssertionError(f"pools do not sit on 4 distinct chips: {chips}")
    return {"errors": errors, "pool_chips": chips}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a four-chip host")
    args = ap.parse_args(argv)
    devs = _device_check(args.chips)
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    AUTOTUNE_DIR.mkdir(exist_ok=True)
    smoke = Smoke(CompileClock(), devs[0])
    if args.chips == 4:
        smoke.phase("sharded_plan", phase_sharded_plan, 32, 512)
        smoke.phase("four_host_service", phase_four_host_service,
                    (8, 16, 24, 32), 512)
    else:
        smoke.phase("paper_plan", phase_paper_plan, 32, 512)
        smoke.phase("served", phase_served, 32)
        smoke.phase("autotune", phase_autotune, 16)
        smoke.phase("megakernel", phase_megakernel, (16, 32))
        smoke.phase("other_plans", phase_other_plans, 32, 512)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
