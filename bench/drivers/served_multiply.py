"""Traffic kind ``served_multiply``: a closed loop of callers through ``SU3Service``.

Each caller re-submits its own canonical gauge field (a pool made in set-up,
one field per caller) with a fresh seeded link set B, and waits for its
result before submitting again.  The chain depths k follow one seeded
sequence that every caller walks in step: each block of len(k) requests
holds every depth once, in a seeded order, so the callers' requests meet in
one (L, k) bucket and coalesce into one dispatch of ``callers`` fields.  The
callers share one thread: submit for every idle caller, one scheduling turn
of the service, then collect what completed.

The window runs ``seconds``, then every caller finishes the block it is in:
each caller completes whole blocks, so every window serves the same mix of
depths whatever the seed and however fast the service is.  The window ends
at the last completion.  ``served_gflops`` is 864 x sites x k summed over
the requests completed in it, over it; ``request_p50_s`` is the median time
from ``submit`` to ``pop_result`` on the caller's clock over those requests.
The check compares a seeded sample of the completed requests, with the
latest of the longest chain in it, with the plain reference.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from bench import counts, data
from bench.harness import Cell, Window, annotations, info

# After the deadline the callers finish their blocks; a request that has
# not come within this long of the deadline never comes.
DRAIN_S = 120.0


@dataclasses.dataclass
class Request:
    caller: int
    rid: int
    k: int
    b: np.ndarray
    submit_s: float


class Depths:
    """The seeded sequence of chain depths every caller walks: blocks of
    ``ks``, each in a seeded order."""

    def __init__(self, seed: int, ks: list[int]):
        self.gen = data.rng(seed, 20)
        self.ks = list(ks)
        self.seq: list[int] = []

    def __getitem__(self, i: int) -> int:
        while len(self.seq) <= i:
            self.seq.extend(int(k) for k in self.gen.permutation(self.ks))
        return self.seq[i]


@dataclasses.dataclass
class State:
    cell: Cell
    svc: Any
    fields: list[np.ndarray]
    depths: Depths
    links: list[Any]  # per caller: a generator of fresh B
    sent: list[int]  # per caller: requests submitted so far


def _links(seed: int, caller: int):
    gen = data.rng(seed, 21 + caller)
    while True:
        yield data.links(gen)


def setup(cell: Cell, previous: State | None = None) -> State:
    from repro.serve.su3 import ServiceConfig, SU3Service
    from repro.core.su3.layouts import Layout

    cfg, p = cell.config, cell.params
    t0 = time.perf_counter()
    fields = [data.gauge_field(data.rng(cell.seed, 10 + i), cfg["L"])
              for i in range(p["callers"])]
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    if previous is not None:
        svc = previous.svc
    else:
        svc = SU3Service(ServiceConfig(
            dtype=cfg["dtype"], layout=Layout(cfg["layout"]),
            compression=cfg["compression"], autotune=False, tile=cfg["tile"]))
        # the callers always coalesce: only their batch size is dispatched
        svc.warm((cfg["L"],), ks=tuple(p["k"]), batch_sizes=(p["callers"],))
        # warm() compiles the dispatches but not the service's slicing of a
        # batch's results: one request round does
        b = data.links(data.rng(cell.seed, 40))
        rids = [svc.submit(f, b, k=p["k"][0]) for f in fields]
        svc.run_until_drained()
        for rid in rids:
            svc.pop_result(rid)
    info(plan=svc.runner_for(cfg["L"]).plan.describe(), data_s=data_s,
         warm_s=time.perf_counter() - t0)
    return State(cell, svc, fields, Depths(cell.seed, p["k"]),
                 [_links(cell.seed, i) for i in range(p["callers"])],
                 [0] * p["callers"])


class _Sample:
    """A seeded reservoir of completed requests, plus the latest request of
    the longest chain."""

    def __init__(self, seed: int, size: int, longest: int):
        self.gen = data.rng(seed, 30)
        self.size, self.longest = size, longest
        self.seen = 0
        self.items: list[tuple[Request, Any]] = []
        self.last_longest: tuple[Request, Any] | None = None

    def offer(self, req: Request, out: Any) -> None:
        if req.k == self.longest:
            self.last_longest = (req, out)
        if self.seen < self.size:
            self.items.append((req, out))
        else:
            j = int(self.gen.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = (req, out)
        self.seen += 1

    def kept(self) -> list[tuple[Request, Any]]:
        out = list(self.items)
        if self.last_longest is not None and all(
                r.rid != self.last_longest[0].rid for r, _ in out):
            out.append(self.last_longest)
        return out


def window(st: State, seconds: float, trace: bool) -> Window:
    from repro.obs.tracer import NULL_TRACER, Tracer

    p, svc = st.cell.params, st.svc
    block = len(p["k"])
    ann = annotations(trace)
    svc.tracer = Tracer() if trace else NULL_TRACER
    sample = _Sample(st.cell.seed, p["sample"], max(p["k"]))
    per_k = counts.multiply(st.cell.config["L"], st.cell.config["dtype"]).flops
    outstanding: dict[int, Request] = {}
    latencies: list[float] = []
    flops = 0.0
    attempted = failed = 0
    last_s = 0.0

    def collect() -> None:
        nonlocal flops, failed, last_s
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
            flops += per_k * req.k
            sample.offer(req, out)

    # every window starts a block, so each serves whole blocks
    start = -(-max(st.sent) // block) * block
    st.sent = [start] * len(st.sent)
    stop_at: int | None = None  # after the deadline: the end of the block
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        for c in range(p["callers"]):
            if c in outstanding or (stop_at is not None and st.sent[c] >= stop_at):
                continue
            k, b = st.depths[st.sent[c]], next(st.links[c])
            st.sent[c] += 1
            t = time.perf_counter()
            with ann("bench.submit"):
                rid = svc.submit(st.fields[c], b, k=k)
            attempted += 1
            if rid is None:
                failed += 1
            else:
                outstanding[c] = Request(c, rid, k, b, t)
        if not outstanding:
            break
        with ann("bench.service_step"):
            svc.step()
        collect()
        now = time.perf_counter()
        if stop_at is None and now >= deadline:
            stop_at = -(-max(st.sent) // block) * block
        if now >= deadline + DRAIN_S:
            break
    failed += len(outstanding)  # never came
    elapsed = (last_s or time.perf_counter()) - t0
    spans = [{"name": s.name, "dur_s": s.dur_s, "attrs": dict(s.attrs)}
             for s in svc.tracer.spans()] if trace else []
    svc.tracer = NULL_TRACER
    p50 = float(np.median(latencies)) if latencies else float("inf")
    return Window(seconds=elapsed, attempted=attempted, failed=failed,
                  metrics={"served_gflops": flops / elapsed / 1e9,
                           "request_p50_s": p50},
                  counters={"completed": len(latencies), "spans": spans},
                  kept={"sample": sample.kept()})


def release(st: State, win: Window) -> None:
    """Results are canonical host arrays already; nothing stays on the chip
    but the service's warm pool, which the check does not need."""
    win.kept["sample"] = [(req, np.asarray(out)) for req, out in win.kept["sample"]]


def checks(st: State, win: Window) -> dict[str, float]:
    ref = st.cell.reference
    errs = [ref.max_abs_err(out, ref.chain(st.fields[req.caller], req.b, req.k))
            for req, out in win.kept["sample"]]
    return {"max_abs_err": max(errs) if errs else float("inf")}


def control(st: State, win: Window) -> dict[str, float]:
    ref = st.cell.reference
    errs = [ref.max_abs_err(ref.chain_bf16(st.fields[req.caller], req.b, req.k),
                            ref.chain(st.fields[req.caller], req.b, req.k))
            for req, _out in win.kept["sample"]]
    return {"max_abs_err": max(errs) if errs else float("inf")}
