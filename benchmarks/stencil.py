"""Stencil benchmark: GFLOPS + overlap efficiency of the Dslash-style path.

The first workload in this repo where halo traffic actually moves.  Four
row families land in ``BENCH_su3.json`` under ``stencil``:

  measured rows   ``stencil_L{L}_{dtype}[_acc][_two_row]_{overlap|serial}`` —
                  wall-time GFLOPS (useful flops = 576/site) of the
                  overlapped vs non-overlapped ``ExecutionPlan.stencil_step``
                  on the local mesh, verified against the uniform fixed
                  point.  ``_two_row`` rows stream the 12-real compressed
                  gauge field (102 words/site instead of 150) and carry the
                  smaller ``bytes_per_site`` — the acceptance bar's
                  bandwidth reduction is read straight off these rows.
  roofline rows   ``stencil_roofline_h{hosts}_{serial|overlap|overlap_d2}
                  [_two_row]`` — the halo-charging model
                  (autotune.predict_stencil) at 1/2/4 hosts across the
                  (overlap, depth) schedule grid.  The bandwidth term
                  INCLUDES the vector-field halo bytes amortized over the
                  exchange depth (``bandwidth_bytes = streamed +
                  halo/depth``).
  overlap row     ``stencil_overlap_identity`` — a forced-device 2-host
                  subprocess runs both schedules on a real sharded mesh and
                  reports bit-identity plus the measured overlap efficiency
                  (t_serial / t_overlap).  On CPU interpret the three
                  dispatches serialize, so efficiency ~<= 1 here; the
                  schedule claim on CPU is dispatch-ORDER only — see
                  ROADMAP for the TPU validation item.
  attribution     ``stencil_phase_attribution_h{hosts}_d{depth}`` — the
                  traced schedule's per-phase seconds (exchange / interior
                  / boundary spans, ``repro.obs``) joined against
                  ``predict_stencil`` at the SAME (overlap, depth, hosts)
                  config: measured-vs-modeled delta and which term
                  dominates.  The identity row additionally carries
                  ``overlap_efficiency_measured = sum_phases /
                  t_overlap_untraced`` — the phase-accounted form of the
                  efficiency the untraced walls can only infer.
  depth-2 rows    ``stencil_depth2_identity_h{hosts}`` — a forced-device
                  subprocess builds 1/2/4-host meshes and checks the
                  communication-avoiding depth-2 step (ONE widened exchange,
                  TWO stencil applications, intermediate ghost ring
                  recomputed locally) bit-identical to two depth-1 steps,
                  for both the 18-real and two-row compressed plans.

Standalone CLI:  PYTHONPATH=src python -m benchmarks.stencil --quick
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from repro.core import autotune
from repro.core.su3.layouts import WORD_BYTES, Layout
from repro.core.su3.plan import EngineConfig, build_plan
from repro.kernels.su3_stencil import (
    STENCIL_COMP_WORDS_PER_SITE,
    STENCIL_FLOPS_PER_SITE,
    STENCIL_WORDS_PER_SITE,
)

# prefixed with an `L, tile, reps = ...` line by _overlap_identity_row (the
# template itself contains JSON braces, so str.format is off the table)
_OVERLAP_SUBPROC = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np, jax
from repro.core.su3.plan import EngineConfig, build_plan
from repro.launch.mesh import MeshSpec

cfg = EngineConfig(L=L, tile=tile, iterations=1, warmups=0)
plan = build_plan(cfg, MeshSpec(hosts=2, devices_per_host=1))
u, v = plan.init_stencil_data()
serial, overlap = plan.stencil_step(overlap=False), plan.stencil_step(overlap=True)
r_s, r_o = serial(u, v), overlap(u, v)  # warm both
r_s.block_until_ready(); r_o.block_until_ready()
identical = bool(np.array_equal(np.asarray(jax.device_get(r_s)),
                                np.asarray(jax.device_get(r_o))))
def best(step):
    t = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter(); step(u, v).block_until_ready()
        t = min(t, time.perf_counter() - t0)
    return t
t_serial, t_overlap = best(serial), best(overlap)
# traced passes AFTER the untraced timings: per-phase spans synchronize at
# phase boundaries (repro.obs), so they measure the phases, not the hiding
from repro.obs import Tracer
plan.tracer = Tracer(enabled=True, capacity=4096)
for _ in range(reps):
    overlap(u, v)
print(json.dumps({
    "identical": identical, "verified": bool(plan.verify_stencil(r_o)),
    "t_serial_s": t_serial, "t_overlap_s": t_overlap,
    "halo": plan.stencil_halo().as_dict(),
    "spans": [s.as_dict() for s in plan.tracer.spans()],
    "device_kind": jax.devices()[0].device_kind,
}))
"""

# prefixed with `L, tile, reps = ...`; 4 forced devices cover 1/2/4-host
# meshes in one process (the forced count locks at first jax init)
_DEPTH2_SUBPROC = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core.su3.plan import EngineConfig, build_plan
from repro.launch.mesh import MeshSpec

rows = []
for hosts in (1, 2, 4):
    for compression in ("none", "two_row"):
        cfg = EngineConfig(L=L, tile=tile, iterations=1, warmups=0,
                           compression=compression)
        mesh = None if hosts == 1 else MeshSpec(hosts=hosts, devices_per_host=1)
        plan = build_plan(cfg, mesh)
        u, v = plan.init_stencil_data()
        step1 = plan.stencil_step(overlap=hosts > 1, depth=1)
        step2 = plan.stencil_step(overlap=hosts > 1, depth=2)
        two = step1(u, step1(u, v)); two.block_until_ready()
        one = step2(u, v); one.block_until_ready()
        identical = bool(np.array_equal(np.asarray(jax.device_get(one)),
                                        np.asarray(jax.device_get(two))))
        def best(fn):
            t = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter(); fn().block_until_ready()
                t = min(t, time.perf_counter() - t0)
            return t
        t2x1 = best(lambda: step1(u, step1(u, v)))
        t1x2 = best(lambda: step2(u, v))
        rows.append({
            "hosts": hosts, "compression": compression,
            "identical": identical,
            "t_two_depth1_s": t2x1, "t_one_depth2_s": t1x2,
            "halo_d2": plan.stencil_halo(depth=2).as_dict(),
        })
print(json.dumps(rows))
"""


def _stencil_bytes_per_site(dtype: str, compression: str) -> int:
    words = (STENCIL_COMP_WORDS_PER_SITE if compression == "two_row"
             else STENCIL_WORDS_PER_SITE)
    return words * WORD_BYTES[dtype]


def _measure_row(L: int, dtype: str, accum: str, overlap: bool, tile: int,
                 reps: int, compression: str = "none") -> dict:
    cfg = EngineConfig(L=L, dtype=dtype, accum_dtype=accum, layout=Layout.SOA,
                       tile=tile, iterations=1, warmups=0,
                       compression=compression)
    plan = build_plan(cfg)
    step = plan.stencil_step(overlap=overlap)
    u, v = plan.init_stencil_data()
    out = step(u, v)
    out.block_until_ready()  # warm/compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        step(u, v).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    n_sites = L**4
    acc_tag = f"_acc-{accum}" if accum else ""
    comp_tag = "_two_row" if compression == "two_row" else ""
    sched = "overlap" if overlap else "serial"
    return {
        "name": f"stencil_L{L}_{dtype}{acc_tag}{comp_tag}_{sched}",
        "us_per_call": round(best * 1e6, 1),
        "L": L, "dtype": dtype, "accum_dtype": accum or dtype,
        "compression": compression,
        "overlap": overlap, "tile": tile,
        "GFLOPS": round(STENCIL_FLOPS_PER_SITE * n_sites / best / 1e9, 3),
        "bytes_per_site": _stencil_bytes_per_site(dtype, compression),
        "bandwidth_bytes": _stencil_bytes_per_site(dtype, compression) * n_sites,
        "verified": plan.verify_stencil(out),
        "plan": plan.describe(),
    }


def _roofline_rows(L: int, dtype: str) -> list[dict]:
    rows = []
    for compression in ("none", "two_row"):
        comp_tag = "_two_row" if compression == "two_row" else ""
        for hosts in (1, 2, 4):
            for overlap, depth in ((False, 1), (True, 1), (True, 2)):
                pred = autotune.predict_stencil(
                    autotune.StencilCandidate(
                        tile=min(256, L**3), overlap=overlap, depth=depth),
                    L=L, dtype=dtype, hosts=hosts, compression=compression,
                )
                sched = ("overlap_d2" if depth == 2
                         else "overlap" if overlap else "serial")
                rows.append({
                    "name": f"stencil_roofline_h{hosts}_{sched}{comp_tag}",
                    "bytes_per_site": _stencil_bytes_per_site(dtype, compression),
                    **pred,
                })
    return rows


def _subprocess_json(code: str, timeout: int = 600) -> tuple[dict | list | None, str]:
    """Run ``code`` (a forced-device CPU simulation) in a child process and
    return its last stdout line as JSON.  The child runs on the CPU: the
    parent may hold the accelerator, which one process owns at a time."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=timeout, cwd=root,
    )
    if proc.returncode != 0:
        return None, proc.stderr.strip()[-300:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), ""


def _overlap_identity_row(L: int, tile: int, reps: int) -> dict:
    """Forced-device 2-host schedule comparison (subprocess: the forced
    device count locks at first jax init, exactly like the fig7 dryrun)."""
    code = f"L, tile, reps = {L}, {tile}, {reps}\n" + _OVERLAP_SUBPROC
    payload, err = _subprocess_json(code)
    if payload is None:
        return {"name": "stencil_overlap_identity", "error": err}
    eff = payload["t_serial_s"] / payload["t_overlap_s"]
    row = {
        "name": "stencil_overlap_identity",
        "hosts": 2, "L": L, "tile": tile,
        "identical": payload["identical"],
        "verified": payload["verified"],
        "t_serial_us": round(payload["t_serial_s"] * 1e6, 1),
        "t_overlap_us": round(payload["t_overlap_s"] * 1e6, 1),
        "overlap_efficiency": round(eff, 3),
        # CPU interpret serializes the three dispatches: the schedule here is
        # dispatch-order only; real hiding needs TPU (ROADMAP open item)
        "dispatch_order_only": True,
        **payload["halo"],
    }
    # phase-level accounting (repro.obs): traced spans give the per-phase
    # seconds; dividing their sum by the UNTRACED overlapped wall measures
    # what the schedule actually hides (traced walls can't — each phase
    # blocks so it can be timed at all)
    from repro.obs.attribution import (
        overlap_efficiency, overlap_efficiency_from_spans,
    )
    acct = overlap_efficiency_from_spans(payload.get("spans", []))
    if acct:
        row.update(
            phase_us={k: round(v * 1e6, 1) for k, v in acct["phase_s"].items()},
            sum_phases_us=round(acct["sum_phases_s"] * 1e6, 1),
            overlap_efficiency_measured=round(overlap_efficiency(
                acct["sum_phases_s"], payload["t_overlap_s"]), 3),
            dominant_phase=(max(acct["phase_s"], key=acct["phase_s"].get)
                            if acct["phase_s"] else None),
        )
    # popped by run(); not columns
    row["_spans"] = payload.get("spans", [])
    row["_device_kind"] = payload.get("device_kind")
    return row


def _phase_attribution_rows(payload_spans: list[dict],
                            device_kind: str | None) -> list[dict]:
    """Model-vs-measured rows for the traced schedule configs: the paper's
    attribution method (which roofline term binds, and by how much the
    model misses) applied to the stencil overlap schedule."""
    from repro.obs.attribution import attribution_report

    rows = []
    for arow in attribution_report(payload_spans, device_kind):
        if arow["workload"] != "stencil_schedule":
            continue
        sched = f"h{arow['hosts']}_d{arow['depth']}"
        rows.append({
            "name": f"stencil_phase_attribution_{sched}",
            "L": arow["L"], "tile": arow["tile"], "hosts": arow["hosts"],
            "depth": arow["depth"], "overlap": arow["overlap"],
            "n_steps": arow["n_spans"],
            "measured_us_per_app": round(arow["measured_unit_s"] * 1e6, 1),
            "predicted_us_per_app": (
                round(arow["predicted_s"] * 1e6, 1)
                if arow["predicted_s"] is not None else None),
            "delta_frac": (round(arow["delta_frac"], 3)
                           if arow["delta_frac"] is not None else None),
            "model_dominant": arow["model_dominant"],
            "measured_dominant_phase": arow["measured_dominant_phase"],
            "phase_us": {k: round(v * 1e6, 1)
                         for k, v in arow["phase_s"].items()},
            # the model is the TPU-v5e roofline; CPU-measured deltas are
            # large and expected — the row's value is the phase breakdown
            # and WHICH term dominates, not the absolute seconds
            "model_hw": "tpu_v5e",
        })
    return rows


def _depth2_identity_rows(L: int, tile: int, reps: int) -> list[dict]:
    """Forced-device 1/2/4-host depth-2 bit-identity: ONE widened exchange +
    two applications vs two depth-1 exchange/apply rounds, 18-real and
    two-row plans, all in one subprocess."""
    code = f"L, tile, reps = {L}, {tile}, {reps}\n" + _DEPTH2_SUBPROC
    payload, err = _subprocess_json(code)
    if payload is None:
        return [{"name": "stencil_depth2_identity_h1", "error": err}]
    rows = []
    for p in payload:
        comp_tag = "_two_row" if p["compression"] == "two_row" else ""
        rows.append({
            "name": f"stencil_depth2_identity_h{p['hosts']}{comp_tag}",
            "L": L, "tile": tile, "depth": 2,
            "hosts": p["hosts"], "compression": p["compression"],
            "identical": p["identical"],
            "t_two_depth1_us": round(p["t_two_depth1_s"] * 1e6, 1),
            "t_one_depth2_us": round(p["t_one_depth2_s"] * 1e6, 1),
            # exchanges per two applications: 2 at depth 1, 1 at depth 2
            "exchanges_saved_per_2apps": 1,
            **{f"halo_{k}": v for k, v in p["halo_d2"].items()},
        })
    return rows


def run(quick: bool = True) -> list[dict]:
    L = 4 if quick else 8
    tile = min(128, L**3)
    reps = 2 if quick else 5
    rows = []
    for dtype, accum in (("float32", ""), ("bfloat16", "float32")):
        for compression in ("none", "two_row"):
            for overlap in (False, True):
                rows.append(_measure_row(
                    L, dtype, accum, overlap, tile, reps,
                    compression=compression))
    rows.extend(_roofline_rows(L, "float32"))
    overlap_row = _overlap_identity_row(L, tile=min(64, L**3), reps=reps)
    spans = overlap_row.pop("_spans", [])
    device_kind = overlap_row.pop("_device_kind", None)
    rows.append(overlap_row)
    rows.extend(_phase_attribution_rows(spans, device_kind))
    rows.extend(_depth2_identity_rows(
        2 if quick else 4, tile=min(16, L**3), reps=reps))
    return rows


if __name__ == "__main__":
    for r in run(quick="--quick" in sys.argv[1:]):
        print(r)
