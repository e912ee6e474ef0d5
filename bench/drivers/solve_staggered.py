"""Traffic kind ``solve_staggered``: even/odd staggered CG solves against one
resident gauge field.

A closed loop with one caller.  Set-up makes a seeded hot field (an
independent random SU(3) link per site and direction), prepares it once for
the staggered operator (``ExecutionPlan.prepare_staggered``: phases, the
antiperiodic t boundary and the neighbours' backward links, split by
parity) and packs a seeded pool of even-site right-hand sides on the chip.
Each solve calls ``ExecutionPlan.cg_solve(field, b_e, tol=..., max_iters=...)``
on the next right-hand side of the pool, as a campaign solves many sources
against one field.  ``solve_s`` is the window over the solves completed in
it.  The check solves one right-hand side of the pool, drawn from the seed,
with the plain reference and compares the window's last answer for it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from bench import data
from bench.program import engine_config
from bench.harness import Cell, Window, annotations, info


@dataclasses.dataclass
class State:
    cell: Cell
    plan: Any
    u: np.ndarray  # canonical gauge field, host
    rhs: list[np.ndarray]  # canonical even-site right-hand sides, host
    field: Any  # the prepared staggered field, on the chip
    rhs_p: list[Any]
    start: int  # pool index of the first solve
    checked: int  # pool index the check solves


def _solve(st: State, j: int):
    cfg = st.cell.config
    return st.plan.cg_solve(st.field, st.rhs_p[j], tol=cfg["tol"],
                            max_iters=cfg["max_iters"])


def setup(cell: Cell, previous: State | None = None) -> State:
    import jax

    # a program without the staggered operator fails here, before any data
    from repro.core.su3.plan import StaggeredField, build_plan  # noqa: F401

    cfg, L, pool = cell.config, cell.config["L"], cell.params["rhs_pool"]
    t0 = time.perf_counter()
    u = data.gauge_field(data.rng(cell.seed, 0), L)
    rhs = [data.vector_field(data.rng(cell.seed, 1 + j), L)[: L**4 // 2]
           for j in range(pool)]
    data_s = time.perf_counter() - t0
    plan = previous.plan if previous else build_plan(engine_config(cfg))
    t0 = time.perf_counter()
    field = plan.prepare_staggered(u, cfg["mass"])
    jax.block_until_ready(field)
    prepare_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rhs_p = [plan.pack_half(b) for b in rhs]
    jax.block_until_ready(rhs_p)
    pack_s = time.perf_counter() - t0
    pick = data.rng(cell.seed, 100)
    st = State(cell, plan, u, rhs, field, rhs_p,
               start=int(pick.integers(0, pool)), checked=int(pick.integers(0, pool)))
    t0 = time.perf_counter()
    res = _solve(st, st.start)
    res.x_p.block_until_ready()
    info(plan=plan.describe(), data_s=data_s, prepare_s=prepare_s, pack_s=pack_s,
         field_bytes=field.nbytes, warm_s=time.perf_counter() - t0,
         warm_iterations=res.iterations)
    return st


def window(st: State, seconds: float, trace: bool) -> Window:
    from repro.core.su3.plan import CGError

    pool = len(st.rhs_p)
    ann = annotations(trace)
    kept: dict[int, Any] = {}
    iterations: list[int] = []
    applies: list[int] = []
    attempted = failed = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        j = (st.start + attempted) % pool
        attempted += 1
        try:
            with ann("bench.cg_solve"):
                res = _solve(st, j)
                res.x_p.block_until_ready()
        except CGError:
            failed += 1
            kept.pop(j, None)
        else:
            kept[j] = res.x_p
            iterations.append(res.iterations)
            applies.append(res.dslash_applies)
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    solved = attempted - failed
    return Window(seconds=elapsed, attempted=attempted, failed=failed,
                  metrics={"solve_s": elapsed / solved if solved else float("inf")},
                  counters={"solves": solved, "iterations": iterations,
                            "dslash_applies": applies},
                  kept=kept)


def release(st: State, win: Window) -> None:
    """Bring the kept answers to the host (canonical) and drop device state."""
    win.kept = {j: st.plan.unpack_half(x) for j, x in win.kept.items()}
    st.field = None
    st.rhs_p = []


def _reference(st: State) -> np.ndarray:
    cfg, ref = st.cell.config, st.cell.reference
    x, _it, _rel = ref.solve(st.u, st.rhs[st.checked], cfg["L"], cfg["mass"],
                             tol=cfg["reference_tol"])
    return x


def checks(st: State, win: Window) -> dict[str, float]:
    """A right-hand side never solved in the window has no answer: that
    reads as an infinite error."""
    if st.checked not in win.kept:
        return {"x_rel_err": float("inf")}
    return {"x_rel_err": st.cell.reference.rel_err(
        np.asarray(win.kept[st.checked]), _reference(st))}


def control(st: State, win: Window) -> dict[str, float]:
    cfg, ref = st.cell.config, st.cell.reference
    x = ref.solve_bf16(st.u, st.rhs[st.checked], cfg["L"], cfg["mass"],
                       tol=cfg["tol"],
                       max_iters=int(st.cell.params["control_max_iters"]))
    return {"x_rel_err": ref.rel_err(x, _reference(st))}
