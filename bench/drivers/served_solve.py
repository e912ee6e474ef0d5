"""Traffic kind ``served_solve``: a closed loop of callers through
``SU3Service.submit_solve``.

Each caller holds its own gauge field (made in set-up, one per caller, from
the family the configuration's operator is Hermitian on) and submits it with
a fresh seeded right-hand side, then waits for the solution before submitting
again, as ``served_multiply`` does for multiplies.  The service seats one
solve per host at a time: it packs the request's field and right-hand side,
advances the solve a few CG iterations per scheduling turn and retires it
when the residual crosses the tolerance.  The callers share one thread:
submit for every idle caller, one scheduling turn, then collect what
completed.

After ``seconds`` no caller submits again; the window ends at the last
completion of what was in flight.  ``request_p50_s`` is the median time from
``submit_solve`` to ``pop_result`` on the caller's clock over the requests
completed in it.  The check compares a seeded sample of the completed
solutions with the plain reference.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from bench import data
from bench.harness import Cell, Window, annotations, info

# After the deadline the callers' last requests finish; one that has not come
# within this long of the deadline never comes.
DRAIN_S = 120.0


@dataclasses.dataclass
class Request:
    caller: int
    rid: int
    b: np.ndarray
    submit_s: float


@dataclasses.dataclass
class State:
    cell: Cell
    svc: Any
    fields: list[np.ndarray]
    sources: list[Any]  # per caller: a generator of fresh right-hand sides


def _sources(seed: int, caller: int, L: int):
    gen = data.rng(seed, 51 + caller)
    while True:
        yield data.vector_field(gen, L)


def _submit(st: State, caller: int, b: np.ndarray):
    cfg = st.cell.config
    return st.svc.submit_solve(st.fields[caller], b, tol=cfg["tol"],
                               max_iters=cfg["max_iters"])


def setup(cell: Cell, previous: State | None = None) -> State:
    from repro.serve.su3 import ServiceConfig, SU3Service
    from repro.core.su3.layouts import Layout

    cfg, p = cell.config, cell.params
    t0 = time.perf_counter()
    fields = [data.axis_constant_field(data.rng(cell.seed, 50 + i), cfg["L"])
              for i in range(p["callers"])]
    data_s = time.perf_counter() - t0
    st = State(cell, None, fields,
               [_sources(cell.seed, i, cfg["L"]) for i in range(p["callers"])])
    t0 = time.perf_counter()
    if previous is not None:
        st.svc = previous.svc
    else:
        st.svc = SU3Service(ServiceConfig(
            dtype=cfg["dtype"], layout=Layout(cfg["layout"]),
            compression=cfg["compression"], autotune=False, tile=cfg["tile"]))
        # one request round compiles the seat's packing, the CG iteration and
        # the unpack of the solution
        b = data.vector_field(data.rng(cell.seed, 60), cfg["L"])
        rids = [_submit(st, c, b) for c in range(p["callers"])]
        st.svc.run_until_drained()
        for rid in rids:
            st.svc.pop_result(rid)
    info(plan=st.svc.runner_for(cfg["L"]).plan.describe(), data_s=data_s,
         warm_s=time.perf_counter() - t0)
    return st


class _Sample:
    """A seeded reservoir of completed requests."""

    def __init__(self, seed: int, size: int):
        self.gen = data.rng(seed, 70)
        self.size = size
        self.seen = 0
        self.items: list[tuple[Request, Any]] = []

    def offer(self, req: Request, out: Any) -> None:
        if self.seen < self.size:
            self.items.append((req, out))
        else:
            j = int(self.gen.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = (req, out)
        self.seen += 1


def window(st: State, seconds: float, trace: bool) -> Window:
    from repro.obs.tracer import NULL_TRACER, Tracer

    p, svc = st.cell.params, st.svc
    ann = annotations(trace)
    svc.tracer = Tracer() if trace else NULL_TRACER
    sample = _Sample(st.cell.seed, p["sample"])
    outstanding: dict[int, Request] = {}
    latencies: list[float] = []
    attempted = failed = 0
    last_s = 0.0

    def collect() -> None:
        nonlocal failed, last_s
        for c, req in list(outstanding.items()):
            if not svc.has_result(req.rid):
                continue
            with ann("bench.pop_result"):
                out = svc.pop_result(req.rid)
            last_s = time.perf_counter()
            del outstanding[c]
            if isinstance(out, Exception):
                failed += 1
                continue
            latencies.append(last_s - req.submit_s)
            sample.offer(req, out)

    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        open_ = time.perf_counter() < deadline
        for c in range(p["callers"]):
            if c in outstanding or not open_:
                continue
            b = next(st.sources[c])
            t = time.perf_counter()
            with ann("bench.submit_solve"):
                rid = _submit(st, c, b)
            attempted += 1
            if rid is None:
                failed += 1
            else:
                outstanding[c] = Request(c, rid, b, t)
        if not outstanding:
            break
        with ann("bench.service_step"):
            svc.step()
        collect()
        if time.perf_counter() >= deadline + DRAIN_S:
            break
    failed += len(outstanding)  # never came
    elapsed = (last_s or time.perf_counter()) - t0
    spans = [{"name": s.name, "dur_s": s.dur_s, "attrs": dict(s.attrs)}
             for s in svc.tracer.spans()] if trace else []
    svc.tracer = NULL_TRACER
    p50 = float(np.median(latencies)) if latencies else float("inf")
    return Window(seconds=elapsed, attempted=attempted, failed=failed,
                  metrics={"request_p50_s": p50},
                  counters={"completed": len(latencies), "spans": spans},
                  kept={"sample": sample.items})


def release(st: State, win: Window) -> None:
    """Results are canonical host arrays already; nothing stays on the chip
    but the service's warm pool, which the check does not need."""
    win.kept["sample"] = [(req, np.asarray(out)) for req, out in win.kept["sample"]]


def _reference(st: State, req: Request) -> np.ndarray:
    cfg, ref = st.cell.config, st.cell.reference
    x, _it, _rel = ref.solve(st.fields[req.caller], req.b, cfg["L"], cfg["sigma"],
                             tol=cfg["reference_tol"])
    return x


def checks(st: State, win: Window) -> dict[str, float]:
    """No completed request reads as an infinite error."""
    ref = st.cell.reference
    errs = [ref.rel_err(out, _reference(st, req)) for req, out in win.kept["sample"]]
    return {"x_rel_err": max(errs) if errs else float("inf")}


def control(st: State, win: Window) -> dict[str, float]:
    cfg, ref = st.cell.config, st.cell.reference
    errs = [ref.rel_err(
        ref.solve_bf16(st.fields[req.caller], req.b, cfg["L"], cfg["sigma"],
                       tol=cfg["tol"], max_iters=int(st.cell.params["control_max_iters"])),
        _reference(st, req)) for req, _out in win.kept["sample"]]
    return {"x_rel_err": max(errs) if errs else float("inf")}
