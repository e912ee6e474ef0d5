"""Traffic benchmark for the SU3 serving subsystem (the ``serve`` section).

Load models over ``repro.serve.su3.SU3Service``:

  open loop    Poisson arrivals (exponential inter-arrival gaps) with a mixed
               (L, k) request population, replayed against the wall clock.
               The arrival rate is derived from a measured warm dispatch time
               (offered load ~= OVERLOAD x service capacity), so the queue
               genuinely builds and the batcher's coalescing shows up as
               batch occupancy > 1 — machine-speed independent.
  closed loop  U concurrent users, each submit -> await -> resubmit for R
               rounds: the sustained-throughput view with a fixed population.
  bf16 row     the same request stream served by a bf16-storage /
               f32-accumulate plan pool vs the f32 pool: measured HLO
               bytes/site must drop, results must agree within 1e-2.
  solve row    one CG solve (data-dependent scheduling-turn count) mixed
               with a multiply stream on the same service: multiplies keep
               completing while the solve is in flight (kind alternation),
               the solve retires mid-stream on its residual test, per-kind
               iteration metrics split the work, and the served solution
               matches the plain-jnp reference solver.
  traced row   ONE Poisson stream replayed tracer-off vs tracer-on
               (``repro.obs``): sustained-GFLOPS delta, full request
               lifecycle + stencil exchange/interior/boundary phase
               coverage, trace exported as JSONL + Chrome trace-event
               JSON (``artifacts/serve_trace.jsonl`` /
               ``artifacts/serve_trace.chrome.json``).

Rows land in ``BENCH_su3.json`` under ``serve`` via ``benchmarks.run``;
standalone CLI:

    PYTHONPATH=src python -m benchmarks.serve_traffic --quick
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.su3.layouts import Layout
from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service

OVERLOAD = 4.0  # offered load multiple of one-dispatch service capacity
TILE = 128  # explicit tile for the fixed-plan (non-autotuned) pools

# prefixed with an `L, tile, reps = ...` line by traced_serving; runs the
# 2-host overlap schedule under an enabled tracer (warm pass untraced, so
# only steady-state phases land in the records) and prints the span records
_PHASES_SUBPROC = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from repro.core.su3.plan import EngineConfig, build_plan
from repro.launch.mesh import MeshSpec
from repro.obs import Tracer

plan = build_plan(EngineConfig(L=L, tile=tile, iterations=1, warmups=0),
                  MeshSpec(hosts=2, devices_per_host=1))
u, v = plan.init_stencil_data()
step = plan.stencil_step(overlap=True)
step(u, v).block_until_ready()  # compile + warm untraced
plan.tracer = Tracer(enabled=True, capacity=4096)
for _ in range(reps):
    step(u, v)
print(json.dumps([s.as_dict() for s in plan.tracer.spans()]))
"""


def _random_request(rng: np.random.Generator, n_sites: int):
    """One user's canonical complex (A, B) pair from a seeded host RNG."""
    a = rng.standard_normal((n_sites, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((4, 3, 3, 2)).astype(np.float32)
    return (
        jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64),
        jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64),
    )


def _service(dtype: str = "float32", accum: str = "", use_autotune: bool = False,
             max_queue_depth: int = 256) -> SU3Service:
    return SU3Service(ServiceConfig(
        dtype=dtype, accum_dtype=accum, autotune=use_autotune, tile=TILE,
        batcher=BatcherConfig(
            max_batch=8, warm_batch_sizes=(1, 2, 4, 8),
            max_queue_depth=max_queue_depth,
        ),
    ))


def _measure_step_s(svc: SU3Service, L: int, k: int, batch: int,
                    rng: np.random.Generator) -> float:
    """Warm median dispatch seconds for the (L, k, batch) shape."""
    n_sites = L**4
    times = []
    for _ in range(3):
        for _ in range(batch):
            a, b = _random_request(rng, n_sites)
            svc.submit(a, b, k=k)
        t0 = time.perf_counter()
        svc.step()
        times.append(time.perf_counter() - t0)
        svc.pop_ready()
    return float(np.median(times))


def open_loop(
    n_requests: int, Ls: tuple[int, ...], ks: tuple[int, ...], seed: int,
    use_autotune: bool = False,
) -> dict:
    """Poisson-arrival replay: submit per the schedule, step when work waits."""
    rng = np.random.default_rng(seed)
    svc = _service(use_autotune=use_autotune)
    svc.warm(Ls, ks=ks, batch_sizes=svc.cfg.batcher.warm_batch_sizes)

    # Offered rate: OVERLOAD x one-dispatch service capacity.  A warm
    # full batch of the slowest shape serves max_batch requests per
    # ref_step_s seconds, so capacity ~= max_batch / ref_step_s.
    max_batch = svc.cfg.batcher.max_batch
    ref_step_s = _measure_step_s(svc, max(Ls), max(ks), max_batch, rng)
    rate = OVERLOAD * max_batch / ref_step_s  # requests/sec
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.cumsum(gaps)
    # pre-generate the mixed population outside the timed loop
    population = []
    for i in range(n_requests):
        L = int(rng.choice(Ls))
        k = int(rng.choice(ks))
        population.append((L, k) + _random_request(rng, L**4))

    svc.metrics.reset()  # report the replay only, not the warmup
    t0 = time.perf_counter()
    submitted = 0
    while svc.metrics.completed + svc.metrics.rejected < n_requests:
        now = time.perf_counter() - t0
        while submitted < n_requests and arrivals[submitted] <= now:
            L, k, a, b = population[submitted]
            svc.submit(a, b, k=k)
            submitted += 1
        if svc.pending():
            svc.step()
            svc.pop_ready()  # deliver: don't accumulate C lattices on device
        elif submitted < n_requests:
            time.sleep(min(arrivals[submitted] - now, 0.01))
    wall = time.perf_counter() - t0

    row = dict(svc.metrics.snapshot())
    row.update(
        name="serve_open_loop",
        load="poisson",
        n_requests=n_requests,
        offered_rate_rps=round(rate, 2),
        replay_wall_s=round(wall, 3),
        mix_L=list(Ls),
        mix_k=list(ks),
        # pool keys are (host, L, dtype, layout, tile)
        pool=[f"h{key[0]}/L{key[1]}/{key[2]}/t{key[4]}" for key in svc.pool_keys()],
    )
    return row


def closed_loop(
    users: int, rounds: int, L: int, k: int | None, seed: int,
    use_autotune: bool = False,
) -> dict:
    """Fixed population: U users submit -> drain -> resubmit, R rounds."""
    rng = np.random.default_rng(seed)
    svc = _service(use_autotune=use_autotune)
    n_sites = L**4
    if k is None:
        k = svc.default_k_for(L)  # the autotuned fused depth, not a constant
    svc.warm((L,), ks=(k,), batch_sizes=(min(8, users),))
    svc.metrics.reset()
    for _ in range(rounds):
        ids = []
        for _ in range(users):
            a, b = _random_request(rng, n_sites)
            ids.append(svc.submit(a, b, k=k))
        svc.run_until_drained()
        for rid in ids:
            svc.pop_result(rid)
    row = dict(svc.metrics.snapshot())
    row.update(
        name="serve_closed_loop", load="closed", users=users, rounds=rounds,
        L=L, k=k,
    )
    return row


def _make_slot_service(slots: int, tracer=None) -> SU3Service:
    """Fixed-slot service: every dispatch padded to ``slots``."""
    return SU3Service(ServiceConfig(
        autotune=False, tile=TILE,
        batcher=BatcherConfig(
            max_batch=slots, warm_batch_sizes=(slots,), max_queue_depth=256,
        ),
    ), tracer=tracer)


def _replay_open_loop(
    svc: SU3Service, Ls: tuple[int, ...], ks: tuple[int, ...],
    n_requests: int, rate: float, seed: int, slots: int,
) -> dict:
    """Replay ONE Poisson (L, k) stream (identical per seed) against ``svc``."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n_requests)
    arrivals = np.cumsum(gaps)
    population = [
        (int(rng.choice(Ls)), int(rng.choice(ks)))
        for _ in range(n_requests)
    ]
    population = [
        (L, k) + _random_request(rng, L**4) for L, k in population
    ]
    svc.warm(tuple(sorted(set(Ls))), ks=ks, batch_sizes=(slots,))
    svc.metrics.reset()
    t0 = time.perf_counter()
    submitted = 0
    while svc.metrics.completed + svc.metrics.rejected < n_requests:
        now = time.perf_counter() - t0
        while submitted < n_requests and arrivals[submitted] <= now:
            _L, k, a, b = population[submitted]
            svc.submit(a, b, k=k)
            submitted += 1
        if svc.pending():
            svc.step()
            svc.pop_ready()
        elif submitted < n_requests:
            time.sleep(min(arrivals[submitted] - now, 0.01))
    return svc.metrics.snapshot()


def traced_serving(
    L: int = 2, n_requests: int = 16, seed: int = 0, slots: int = 4,
    ks: tuple[int, ...] = (1, 2), n_stencil: int = 4,
    stencil_L: int = 4, trace_prefix: str = "artifacts/serve_trace",
) -> dict:
    """Tracing-overhead and lifecycle/phase-coverage row (``repro.obs``).

    Replays ONE Poisson mixed-k stream twice — tracer disabled (the
    production default: every hot-path site is one ``tracer.enabled``
    predicate) and enabled (flight-recorder ring) — and reports the
    sustained-GFLOPS delta between the two.  The traced service then
    serves a short stencil stream, and the 2-host overlap schedule runs
    under the SAME tracer (oversubscribed on the local device), so one
    exported trace covers the full request lifecycle (admit -> dispatch ->
    complete, the queue wait an attribute of the request span, multiply AND
    stencil kinds) plus the
    stencil exchange/interior/boundary phases.  The row asserts both
    coverages and names the trace files (``{trace_prefix}.jsonl`` and
    ``{trace_prefix}.chrome.json`` — the latter loads in
    chrome://tracing / Perfetto and carries the provenance block in
    ``otherData``).
    """
    from repro.obs import Tracer, attribution_report, provenance_block

    probe = _make_slot_service(slots)
    rng = np.random.default_rng(seed)
    probe.warm((L,), ks=ks, batch_sizes=(slots,))
    iter_s = _measure_step_s(probe, L, 1, slots, rng)
    rate = 1.5 / max(iter_s, 1e-5)

    # min-of-N walls: the first replay pays the jit compiles and every
    # replay carries scheduler/sleep jitter; the min discards both while
    # any persistent per-span tracer cost survives
    def best_replay(tracer, reps=3):
        best, svc = None, None
        for _ in range(reps):
            svc = _make_slot_service(slots, tracer=tracer)
            snap = _replay_open_loop(svc, (L,), ks, n_requests, rate, seed, slots)
            if best is None or snap["wall_s"] < best["wall_s"]:
                best = snap
        return best, svc

    off_snap, _ = best_replay(None)
    tracer = Tracer(enabled=True, capacity=1 << 16)
    on_snap, svc = best_replay(tracer)

    # a short stencil stream through the SAME service + tracer (request
    # lifecycle of the second workload kind)
    n_sites = L**4
    for _ in range(n_stencil):
        u, _ = _random_request(rng, n_sites)
        vv = rng.standard_normal((n_sites, 3, 2)).astype(np.float32)
        svc.submit_stencil(u, jnp.asarray(vv[..., 0] + 1j * vv[..., 1],
                                          jnp.complex64))
    svc.run_until_drained()
    svc.pop_ready()

    # the overlap schedule's three phases need a real 2-host mesh; the
    # forced device count locks at first jax init, so (exactly like the
    # stencil benchmark's identity rows) a subprocess runs the traced
    # schedule and its span records merge into THIS trace via absorb()
    from benchmarks.stencil import _subprocess_json
    code = (f"L, tile, reps = {stencil_L}, {min(64, stencil_L**3)}, 2\n"
            + _PHASES_SUBPROC)
    phase_records, phase_err = _subprocess_json(code)
    if phase_records:
        tracer.absorb(phase_records, lane_offset=200)

    names = {s.name for s in tracer.spans()}
    lifecycle = {"admit", "dispatch", "request"}
    phases = {"stencil.exchange", "stencil.interior", "stencil.boundary"}
    jsonl_path = f"{trace_prefix}.jsonl"
    chrome_path = f"{trace_prefix}.chrome.json"
    trace_dir = os.path.dirname(trace_prefix)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)  # gitignored artifacts/ home
    prov = provenance_block()
    n_records = tracer.to_jsonl(jsonl_path, metadata=prov) - 1  # less meta
    tracer.to_chrome_trace(chrome_path, metadata=prov)
    # tracing cost shows up in the replay wall of the identical Poisson
    # schedule (busy_s can NOT see it: spans are recorded outside the timed
    # dispatch region by design); at quick scale the delta is noise-level —
    # which is the acceptance point
    row = {
        "name": "serve_traced",
        "L": L, "mix_k": list(ks), "n_requests": n_requests, "slots": slots,
        "n_stencil_requests": n_stencil, "stencil_hosts": 2,
        "stencil_L": stencil_L,
        "gflops_untraced": off_snap["sustained_gflops_wall"],
        "gflops_traced": on_snap["sustained_gflops_wall"],
        "wall_s_untraced": off_snap["wall_s"],
        "wall_s_traced": on_snap["wall_s"],
        "tracing_overhead_frac": round(
            on_snap["wall_s"] / max(off_snap["wall_s"], 1e-9) - 1.0, 4),
        "spans_recorded": n_records,
        "spans_dropped": tracer.dropped,
        "lifecycle_covered": lifecycle <= names,
        "phases_covered": phases <= names,
        "span_names": sorted(names),
        "attribution_rows": len(attribution_report(
            tracer.spans(), prov["device_kind"])),
        "trace_jsonl": jsonl_path,
        "trace_chrome": chrome_path,
    }
    if phase_err:
        row["phase_subprocess_error"] = phase_err
    return row


def bf16_plan_comparison(L: int, seed: int) -> dict:
    """bf16-storage/f32-accumulate pool vs f32 pool on one request stream.

    The serving form of the ROADMAP's bf16 item: storage bytes drop at the
    HLO level (measured, not modeled) while results stay within 1e-2 of the
    f32 path and the canonical su3_bench verification still passes.
    """
    rng = np.random.default_rng(seed)
    n_sites = L**4
    f32 = _service()
    bf16 = _service(dtype="bfloat16", accum="float32")
    reqs = [_random_request(rng, n_sites) for _ in range(4)]
    ids32 = [f32.submit(a, b, k=2) for a, b in reqs]
    ids16 = [bf16.submit(a, b, k=2) for a, b in reqs]
    f32.run_until_drained()
    bf16.run_until_drained()
    errs = []
    for i32, i16 in zip(ids32, ids16):
        c32, c16 = f32.pop_result(i32), bf16.pop_result(i16)
        errs.append(
            float(jnp.max(jnp.abs(c16 - c32)))
            / max(float(jnp.max(jnp.abs(c32))), 1.0)
        )
    err = max(errs)

    # canonical verification through the bf16 plan itself
    plan16 = bf16.runner_for(L).plan
    a_phys, b_p, _, _ = plan16.init_data()
    verified = plan16.verify(plan16.step(a_phys, b_p))

    hlo_f32 = autotune.hlo_bytes_for_variant(
        "pallas", Layout.SOA, n_sites=1024, tile=TILE)
    hlo_bf16 = autotune.hlo_bytes_for_variant(
        "pallas", Layout.SOA, n_sites=1024, tile=TILE,
        dtype="bfloat16", accum_dtype="float32")
    return {
        "name": "serve_bf16_vs_f32",
        "L": L,
        "hlo_bytes_per_site_f32": round(hlo_f32, 1),
        "hlo_bytes_per_site_bf16": round(hlo_bf16, 1),
        "bf16_bytes_ratio": round(hlo_bf16 / hlo_f32, 3),
        "bf16_fewer_bytes": hlo_bf16 < hlo_f32,
        "model_bytes_per_site_f32": 2 * 72 * 4,
        "model_bytes_per_site_bf16": 2 * 72 * 2,
        "max_rel_err_vs_f32": round(err, 5),
        "within_1e-2": err < 1e-2,
        "bf16_verified": bool(verified),
        "plan": plan16.describe(),
    }


def solve_mix(L: int = 2, n_multiply: int = 6, seed: int = 0,
              iters_per_step: int = 2) -> dict:
    """Mixed solve + multiply traffic: the data-dependent-length request kind.

    One CG solve (unknown-many scheduling turns: it retires on a residual
    test, not a known chain depth) rides the SAME service as a stream of
    multiply requests.  The acceptance points this row records:

      * kind alternation keeps the multiplies flowing WHILE the solve is in
        flight (``multiplies_done_mid_solve`` > 0 — no starvation either way);
      * the solve retires mid-stream the moment its residual crosses tol —
        not at a padded max_iters — freeing its host budget
        (``solve_iterations`` < max_iters);
      * per-kind iteration metrics split the work
        (``kind_iterations['solve']`` == solve iterations dispatched);
      * the served solution matches the plain-jnp :func:`cg_reference_solve`
        oracle on the identical problem.
    """
    from benchmarks.cg_solve import _problem
    from repro.core.su3.plan import CG_SHIFT, cg_reference_solve

    rng = np.random.default_rng(seed)
    n_sites = L**4
    svc = SU3Service(ServiceConfig(
        autotune=False, tile=min(TILE, n_sites),
        solve_iters_per_step=iters_per_step,
        batcher=BatcherConfig(
            max_batch=4, warm_batch_sizes=(1, 2, 4), max_queue_depth=64,
        ),
    ))
    u, b = _problem(L)
    tol = 1e-6
    max_iters = 64
    solve_id = svc.submit_solve(u, b, tol=tol, max_iters=max_iters)
    mult_ids = [svc.submit(*_random_request(rng, n_sites), k=1)
                for _ in range(n_multiply)]

    solve_x = None
    solve_done_step = None
    mult_done_mid_solve = 0
    steps = 0
    t0 = time.perf_counter()
    while svc.pending():
        steps += 1
        svc.step()
        for rid, out in svc.pop_ready().items():
            if rid == solve_id:
                solve_done_step = steps
                solve_x = out
            elif solve_done_step is None:
                mult_done_mid_solve += 1
    wall = time.perf_counter() - t0

    x_ref, _, _ = cg_reference_solve(u, b, L, sigma=CG_SHIFT, tol=tol,
                                     max_iters=max_iters)
    err = float(jnp.max(jnp.abs(solve_x - x_ref))) / max(
        float(jnp.max(jnp.abs(x_ref))), 1e-30)
    snap = svc.metrics.snapshot()
    kind_iters = snap.get("kind_iterations", {})
    solve_iters = kind_iters.get("solve", 0)
    return {
        "name": "serve_solve_mix",
        "L": L,
        "n_multiply": n_multiply,
        "solve_iters_per_step": iters_per_step,
        "tol": tol,
        "max_iters": max_iters,
        "steps": steps,
        "wall_s": round(wall, 3),
        "solve_retired_step": solve_done_step,
        "solve_iterations": solve_iters,
        "solve_retired_early": 0 < solve_iters < max_iters,
        "multiplies_done_mid_solve": mult_done_mid_solve,
        "kinds_interleaved": mult_done_mid_solve > 0,
        "kind_iterations": kind_iters,
        "completed": snap["completed"],
        "solve_max_rel_err_vs_reference": round(err, 9),
        "solve_matches_reference": err < 1e-5,
    }


def run(quick: bool = True, seed: int = 0, use_autotune: bool = False) -> list[dict]:
    """The ``serve`` benchmark section (wired into benchmarks.run)."""
    if quick:
        Ls, ks, n_req, users, rounds = (2, 4), (1, 2), 32, 8, 2
    else:
        Ls, ks, n_req, users, rounds = (2, 4), (1, 2, 4), 96, 8, 4
    rows = [
        open_loop(n_req, Ls, ks, seed, use_autotune=use_autotune),
        closed_loop(users, rounds, max(Ls), None if use_autotune else max(ks),
                    seed, use_autotune=use_autotune),
        bf16_plan_comparison(max(Ls), seed),
        traced_serving(min(Ls), n_requests=12 if quick else 32, seed=seed),
        solve_mix(min(Ls), n_multiply=4 if quick else 8, seed=seed),
    ]
    return rows


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="build pools through the persistent autotune cache "
                         "(first run pays the tile+K sweeps)")
    args = ap.parse_args(argv)
    rows = run(quick=args.quick, seed=args.seed, use_autotune=args.autotune)
    ok = True
    for r in rows:
        print(r)
        if r["name"] == "serve_open_loop" and r["mean_live_batch"] <= 1.0:
            print("FAIL: open-loop batch occupancy did not exceed 1", file=sys.stderr)
            ok = False
        if r["name"] == "serve_bf16_vs_f32" and not (
            r["bf16_fewer_bytes"] and r["within_1e-2"] and r["bf16_verified"]
        ):
            print("FAIL: bf16-storage plan acceptance", file=sys.stderr)
            ok = False
        if r["name"] == "serve_solve_mix" and not (
            r["solve_retired_early"] and r["kinds_interleaved"]
            and r["solve_matches_reference"]
        ):
            print("FAIL: solve-mix acceptance (early retire / interleave / "
                  "reference match)", file=sys.stderr)
            ok = False
        if r["name"] == "serve_traced" and not (
            r["lifecycle_covered"] and r["phases_covered"]
        ):
            print("FAIL: trace did not cover the request lifecycle and the "
                  "stencil exchange/interior/boundary phases", file=sys.stderr)
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
