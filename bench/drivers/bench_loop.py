"""Traffic kind ``bench_loop``: SU3_Bench's timed loop through ``ExecutionPlan.step``.

C = A x B from the same resident random-SU(3) A and B in every iteration,
dispatched back to back with at most ``max_in_flight`` iterations queued on
the device, so outputs do not pile up in HBM.  The TPU runtime lets 32
executions wait at once and holds further dispatches until one ends, so 32
(128 ms of device work) is as far ahead of the chip as the host can get.
``bench_gflops`` is the paper's 864 flops per site times sites times
iterations completed, over the window.  The check compares outputs of the
window itself (the one at a seeded early iteration and the last) with the
plain reference.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Any

import numpy as np

from bench import counts, data
from bench.program import engine_config
from bench.harness import Cell, Window, annotations, info


@dataclasses.dataclass
class State:
    cell: Cell
    plan: Any
    a: np.ndarray  # canonical A, host
    b: np.ndarray  # canonical B, host
    a_phys: Any  # device
    b_p: Any  # device
    keep_at: int  # the early iteration whose output is compared


def setup(cell: Cell, previous: State | None = None) -> State:
    import jax

    from repro.core.su3.plan import build_plan

    cfg = cell.config
    t0 = time.perf_counter()
    gen = data.rng(cell.seed, 0)
    a, b = data.gauge_field(gen, cfg["L"]), data.links(gen)
    data_s = time.perf_counter() - t0
    plan = previous.plan if previous else build_plan(engine_config(cfg))
    t0 = time.perf_counter()
    a_phys, b_p = plan.pack_gauge(a), plan.pack_links(b)
    jax.block_until_ready((a_phys, b_p))
    pack_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(2):
        plan.step(a_phys, b_p).block_until_ready()
    info(plan=plan.describe(), data_s=data_s, pack_s=pack_s,
         warm_s=time.perf_counter() - t0)
    keep_at = int(data.rng(cell.seed, 1).integers(0, cell.params["keep_within"]))
    # the window allocates little; the collector need not scan the set-up's heap in it
    gc.collect()
    gc.freeze()
    return State(cell, plan, a, b, a_phys, b_p, keep_at)


def window(st: State, seconds: float, trace: bool) -> Window:
    depth = st.cell.params["max_in_flight"]
    ann = annotations(trace)
    inflight: collections.deque = collections.deque()
    kept: dict[str, Any] = {}
    n = 0
    t0 = time.perf_counter()
    done = [t0]  # when each retired iteration was seen done
    deadline = t0 + seconds
    collector = CollectorClock()
    gc.callbacks.append(collector)
    try:
        while True:
            with ann("bench.step"):
                c = st.plan.step(st.a_phys, st.b_p)
            if n == st.keep_at:
                kept["early"] = c
            inflight.append(c)
            n += 1
            if len(inflight) >= depth:
                with ann("bench.wait"):
                    inflight.popleft().block_until_ready()
                done.append(time.perf_counter())
            if done[-1] >= deadline:
                break
        with ann("bench.wait"):  # the trace spans the drain too
            for c in inflight:
                c.block_until_ready()
        elapsed = time.perf_counter() - t0
    finally:
        gc.callbacks.remove(collector)
    kept["last"] = c
    stalls(done)
    info(collections=collector.count, collection_s=collector.seconds)
    work = counts.multiply(st.cell.config["L"], st.cell.config["dtype"])
    return Window(seconds=elapsed, attempted=n, failed=0,
                  metrics={"bench_gflops": work.flops * n / elapsed / 1e9},
                  counters={"steps": n}, kept=kept)


def stalls(done: list[float]) -> None:
    """Print how the window's time between retired iterations spread: the
    median, the longest, and the time lost in gaps over 4x the median."""
    gaps = np.diff(np.asarray(done))
    if gaps.size < 2:
        return
    med = float(np.median(gaps))
    slow = gaps[gaps > 4 * med]
    info(retire_median_s=med, retire_longest_s=float(gaps.max()),
         slow_gaps=int(slow.size), slow_gap_s=float(np.sum(slow - med)))


class CollectorClock:
    """A ``gc.callbacks`` entry: how often, and how long, the cyclic garbage
    collector ran."""

    def __init__(self) -> None:
        self.count, self.seconds, self._start = 0, 0.0, 0.0

    def __call__(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._start


def release(st: State, win: Window) -> None:
    """Bring the kept outputs to the host (canonical) and drop device state."""
    win.kept = {k: st.plan.unpack(v) for k, v in win.kept.items()}
    st.a_phys = st.b_p = None


def checks(st: State, win: Window) -> dict[str, float]:
    ref = st.cell.reference
    c_ref = ref.chain(st.a, st.b, 1)
    return {"max_abs_err": max(ref.max_abs_err(np.asarray(c), c_ref)
                               for c in win.kept.values())}


def control(st: State, win: Window) -> dict[str, float]:
    ref = st.cell.reference
    c_ref = ref.chain(st.a, st.b, 1)
    return {"max_abs_err": ref.max_abs_err(ref.chain_bf16(st.a, st.b, 1), c_ref)}
