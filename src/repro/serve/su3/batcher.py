"""Dynamic request batching for SU3 lattice serving.

The serving analog of the paper's layout lesson: throughput is decided by
what you fix *before* the hot loop runs.  For traffic, that is the batch
shape — every distinct (lattice size, chain depth, batch size) triple is a
separate compiled dispatch, so an unmanaged request stream recompiles
constantly and runs batch-of-one.  The batcher makes the batch shape a
controlled, warm quantity:

  * **bucketing** — arriving requests are queued per ``(L, k)`` bucket
    (lattice size x chain depth); only shape-compatible requests coalesce
    into one vmapped dispatch.
  * **warm batch sizes** — a coalesced batch is padded up to the nearest
    size in ``warm_batch_sizes``, so the jit cache holds a handful of
    compiled batch shapes instead of one per observed batch size.  The
    padding cost is explicit: ``CoalescedBatch.occupancy`` is the live
    fraction, and the metrics charge padded slots as overhead.
  * **admission control** — ``submit`` rejects when the total queued depth
    would exceed ``max_queue_depth`` (backpressure to the caller), bounding
    queue-growth latency instead of letting p99 run away under overload.

The batcher is a plain steppable object — no threads, no event loop — so it
drops into a synchronous replay harness (benchmarks/serve_traffic.py), an
asyncio front-end (``SU3Service.arun``), or a test with the same semantics.

One scheduling policy is layered on top (host-side bookkeeping only — no
jax in this module):

  * **locality routing** — :class:`LocalityRouter` pins each lattice size L
    to one host, sticky after first sight: the host that paid the compile +
    tile sweep for an L's warm runner keeps serving that L (the serving
    analog of the paper's first-touch rule — work follows the warm data).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any

from repro.serve.su3.tenancy import DEFAULT_TENANT, SLO_BULK, GroupKey

BucketKey = tuple[int, int]  # (L, chain depth k)


@dataclasses.dataclass
class ServeRequest:
    """One user's lattice request.

    ``kind="multiply"`` (default): C = A (x) B chained ``k`` times, with
    ``a`` the canonical lattice and ``b`` the (4, 3, 3) link matrix set.
    ``kind="stencil"``: one application of the nearest-neighbor Dslash-style
    operator, with ``a`` the canonical gauge lattice and ``b`` the canonical
    color-vector field (n_sites, 3); ``k`` is always 1 (the stencil is not
    chained — its output is a vector field, not a lattice).
    ``kind="solve"``: a CG solve with ``a`` the canonical gauge lattice:
    with ``mass`` None on the site-local-adjoint operator
    ``(sigma I + S) x = b``, ``b`` the canonical right-hand side
    (n_sites, 3); with a ``mass`` on the even/odd staggered operator
    ``(4 m^2 - D_eo D_oe) x_e = b_e``, ``b`` an even-site vector
    (n_sites // 2, 3).  ``tol``/``max_iters`` bound the solver and the
    request's iteration count is DATA-DEPENDENT — the service advances it a
    few CG iterations per scheduling turn and it retires the turn its
    residual crosses tol.
    """

    req_id: int
    a: Any  # canonical complex (n_sites, 4, 3, 3)
    b: Any  # canonical complex (4, 3, 3) | (n_sites, 3) for stencil/solve
    # | (n_sites // 2, 3) for a staggered solve
    L: int
    k: int
    arrival_s: float = 0.0  # perf_counter timestamp at admission
    kind: str = "multiply"  # "multiply" | "stencil" | "solve"
    seated_s: float = 0.0  # perf_counter timestamp when seated (batch popped)
    # (0.0 until seated; the request-lifecycle span derives queue_wait from it)
    tol: float = 0.0  # solve: relative-residual convergence target
    max_iters: int = 0  # solve: iteration cap (retires unconverged at cap)
    mass: float | None = None  # solve: the staggered operator's valence mass
    # (None = the site-local-adjoint operator)
    deadline_s: float = 0.0  # absolute perf_counter deadline (0 = none); a
    # request past it is EVICTED (queue slot or active solve seat freed)
    # and completes with a structured DeadlineExceededError
    priority: int = 0  # shedding priority (robustness.PRIORITY[kind]): under
    # backpressure, lower priorities shed first to admit higher ones
    attempts: int = 0  # dispatch attempts consumed (retry accounting)
    tenant: str = DEFAULT_TENANT  # tenant identity (quota + fairness group)
    slo: str = SLO_BULK  # SLO class: "latency" (never shed) or "bulk" (the
    # only sheddable lane); defaults bulk so a raw request stays sheddable —
    # the service sets the per-kind class default

    @property
    def n_sites(self) -> int:
        return self.L**4

    @property
    def bucket(self) -> BucketKey:
        return (self.L, self.k)

    @property
    def group(self) -> GroupKey:
        """The (tenant, SLO class) fairness group this request bills to."""
        return (self.tenant, self.slo)


@dataclasses.dataclass(frozen=True)
class BatcherConfig:
    max_batch: int = 8  # hard cap on requests coalesced into one dispatch
    warm_batch_sizes: tuple[int, ...] = (1, 2, 4, 8)  # pad-to sizes (jit cache keys)
    max_queue_depth: int = 64  # admission control: reject submits beyond this

    def __post_init__(self) -> None:
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {self.max_queue_depth} "
                f"(0 would reject every submit and livelock arun retries)"
            )
        if not self.warm_batch_sizes or sorted(self.warm_batch_sizes) != list(
            self.warm_batch_sizes
        ):
            raise ValueError(
                f"warm_batch_sizes must be ascending and non-empty, "
                f"got {self.warm_batch_sizes}"
            )
        if self.max_batch > self.warm_batch_sizes[-1]:
            raise ValueError(
                f"max_batch={self.max_batch} exceeds the largest warm batch "
                f"size {self.warm_batch_sizes[-1]}: batches above it would "
                f"dispatch at never-warmed sizes, recompiling per observed "
                f"batch size"
            )

    def padded_size(self, n: int) -> int:
        """Nearest warm batch size >= n (n itself past the largest warm size)."""
        for w in self.warm_batch_sizes:
            if w >= n:
                return w
        return n


@dataclasses.dataclass
class CoalescedBatch:
    """Shape-compatible requests headed for one vmapped dispatch."""

    key: BucketKey
    requests: list[ServeRequest]
    padded_size: int

    @property
    def L(self) -> int:
        return self.key[0]

    @property
    def k(self) -> int:
        return self.key[1]

    @property
    def occupancy(self) -> float:
        """Live fraction of the dispatched batch (1.0 = no padding waste)."""
        return len(self.requests) / self.padded_size

    @property
    def pad(self) -> int:
        return self.padded_size - len(self.requests)


class DynamicBatcher:
    """Steppable coalescing queue with per-(L, k) buckets and backpressure."""

    def __init__(self, cfg: BatcherConfig | None = None):
        self.cfg = cfg if cfg is not None else BatcherConfig()
        # (group, bucket) -> FIFO of requests; OrderedDict keeps creation
        # order as the tiebreak when head-request arrival times are equal.
        # Keying the families by (tenant, SLO class) FIRST means a coalesced
        # dispatch only ever carries one group's requests — tenant isolation
        # extends into the batch, not just the queue.
        self._buckets: "OrderedDict[tuple[GroupKey, BucketKey], list[ServeRequest]]" \
            = OrderedDict()
        # stencil requests coalesce by L only (no chain depth); they never
        # ride a multiply batch, so they live in their own queue family
        self._stencil: "OrderedDict[tuple[GroupKey, int], list[ServeRequest]]" \
            = OrderedDict()
        # solve requests also queue by L; the service advances ONE active
        # solve per host a few CG iterations per turn, so this family feeds
        # that seat oldest-first
        self._solve: "OrderedDict[tuple[GroupKey, int], list[ServeRequest]]" \
            = OrderedDict()
        self._depth = 0

    def __len__(self) -> int:
        return self._depth

    @property
    def depth(self) -> int:
        return self._depth

    def bucket_depths(self) -> dict[BucketKey, int]:
        """Waiting multiplies per (L, k), aggregated over tenant groups
        (the pre-tenancy key shape every caller and test pins)."""
        out: dict[BucketKey, int] = {}
        for (_g, key), q in self._buckets.items():
            if q:
                out[key] = out.get(key, 0) + len(q)
        return out

    def stencil_depths(self) -> dict[int, int]:
        """Waiting stencil requests per lattice size (all groups)."""
        out: dict[int, int] = {}
        for (_g, L), q in self._stencil.items():
            if q:
                out[L] = out.get(L, 0) + len(q)
        return out

    def solve_depths(self) -> dict[int, int]:
        """Waiting solve requests per lattice size (all groups)."""
        out: dict[int, int] = {}
        for (_g, L), q in self._solve.items():
            if q:
                out[L] = out.get(L, 0) + len(q)
        return out

    # -- tenancy views ---------------------------------------------------------

    def pending_kinds_by_group(self) -> dict[GroupKey, set[str]]:
        """Queued work per (tenant, SLO class) group: group -> kinds with at
        least one waiting request — the fair scheduler's pending set."""
        out: dict[GroupKey, set[str]] = {}
        for kind, (group, _key), q in self._family_items():
            if q:
                out.setdefault(group, set()).add(kind)
        return out

    def depth_for_slo(self, slo: str) -> int:
        """Total queued requests of one SLO class (any tenant, any kind) —
        the brownout ladder's reduced-bulk-budget check."""
        return sum(
            len(q) for _kind, (group, _key), q in self._family_items()
            if group[1] == slo
        )

    def submit(self, req: ServeRequest) -> bool:
        """Admit a request; False under backpressure (queue budget exhausted).
        Multiply requests bucket by (group, (L, k)); stencil and solve
        requests by (group, L) — all families draw on one depth budget."""
        if self._depth >= self.cfg.max_queue_depth:
            return False
        if not req.arrival_s:
            req.arrival_s = time.perf_counter()
        if req.kind == "stencil":
            self._stencil.setdefault((req.group, req.L), []).append(req)
        elif req.kind == "solve":
            self._solve.setdefault((req.group, req.L), []).append(req)
        else:
            self._buckets.setdefault((req.group, req.bucket), []).append(req)
        self._depth += 1
        return True

    def next_solve(self, group: GroupKey | None = None) -> ServeRequest | None:
        """Pop the oldest waiting solve request (across lattice sizes) —
        the service seats it as the host's active solve.  Solves never
        coalesce: each carries its own data-dependent iteration count.
        ``group`` restricts the pop to one (tenant, class) — the fair
        scheduler serves exactly the group that owns the turn."""
        live = [
            (key, q) for (g, key), q in self._solve.items()
            if q and (group is None or g == group)
        ]
        if not live:
            return None
        _L, queue = min(live, key=lambda kv: kv[1][0].arrival_s)
        req = queue.pop(0)
        self._depth -= 1
        return req

    def next_stencil_batch(self, group: GroupKey | None = None) -> CoalescedBatch | None:
        """Coalesce up to ``max_batch`` stencil requests of the most urgent
        lattice size (oldest waiting head first), warm-size padded like the
        multiply buckets.  The batch ``key`` is ``(L, 1)`` — one stencil
        application per request.  ``group`` restricts to one (tenant, class);
        batches never mix groups either way (the families are group-keyed)."""
        live = [
            (key, q) for (g, key), q in self._stencil.items()
            if q and (group is None or g == group)
        ]
        if not live:
            return None
        L, queue = min(live, key=lambda kv: kv[1][0].arrival_s)
        take = queue[: self.cfg.max_batch]
        queue[:] = queue[len(take):]
        self._depth -= len(take)
        return CoalescedBatch(
            key=(L, 1), requests=take, padded_size=self.cfg.padded_size(len(take))
        )

    def next_batch(self, group: GroupKey | None = None) -> CoalescedBatch | None:
        """Coalesce up to ``max_batch`` requests from the most urgent bucket.

        Urgency is head-of-line arrival time (oldest waiting request first),
        so no bucket starves under mixed traffic: a lone L=2 request queued
        behind a stream of L=4 batches is picked as soon as it is oldest.
        ``group`` restricts to one (tenant, class); a batch never mixes
        groups either way — the buckets themselves are group-keyed.
        """
        live = [
            (key, q) for (g, key), q in self._buckets.items()
            if q and (group is None or g == group)
        ]
        if not live:
            return None
        key, queue = min(live, key=lambda kv: kv[1][0].arrival_s)
        take = queue[: self.cfg.max_batch]
        queue[:] = queue[len(take):]
        self._depth -= len(take)
        return CoalescedBatch(
            key=key, requests=take, padded_size=self.cfg.padded_size(len(take))
        )

    # -- robustness views ------------------------------------------------------

    def _family_items(self):
        """Every queue as a (kind, (group, key), queue) triple."""
        for gkey, q in self._buckets.items():
            yield "multiply", gkey, q
        for gkey, q in self._stencil.items():
            yield "stencil", gkey, q
        for gkey, q in self._solve.items():
            yield "solve", gkey, q

    def _families(self):
        """The three queue families as (kind, key, queue) triples (legacy
        key shape: (L, k) for multiplies, L otherwise)."""
        for kind, (_group, key), q in self._family_items():
            yield kind, key, q

    def evict_expired(self, now: float) -> list[ServeRequest]:
        """Pop every queued request whose deadline passed; the caller turns
        them into structured timeouts.  Requests without a deadline
        (``deadline_s == 0``) never expire."""
        evicted: list[ServeRequest] = []
        for _kind, _key, q in self._families():
            keep = []
            for req in q:
                if req.deadline_s and req.deadline_s <= now:
                    evicted.append(req)
                else:
                    keep.append(req)
            q[:] = keep
        self._depth -= len(evicted)
        return evicted

    def shed_lowest(self, max_priority: int,
                    sheddable_slo: str | None = None) -> ServeRequest | None:
        """Pop the YOUNGEST queued request with priority < ``max_priority``
        (the freshest bulk work pays for the latency-sensitive arrival —
        oldest bulk requests have waited longest and keep their place).
        ``sheddable_slo`` additionally restricts victims to one SLO class
        (the service passes "bulk": the latency lane is never shed).
        Returns None when nothing sheddable waits."""
        best: tuple[float, Any, list] | None = None
        for _kind, key, q in self._families():
            for req in q:
                if sheddable_slo is not None and req.slo != sheddable_slo:
                    continue
                if req.priority < max_priority and (
                    best is None or req.arrival_s > best[0]
                ):
                    best = (req.arrival_s, req, q)
        if best is None:
            return None
        _arrival, req, q = best
        q.remove(req)
        self._depth -= 1
        return req

    def drain(self) -> list[ServeRequest]:
        """Pop EVERY queued request (quarantine re-seating: the caller
        resubmits them through the router onto healthy hosts)."""
        out: list[ServeRequest] = []
        for _kind, _key, q in self._families():
            out.extend(q)
            q.clear()
        self._depth = 0
        return out


class LocalityRouter:
    """Sticky (lattice size -> host) routing for a host-sharded warm pool.

    The first request for a lattice size L is assigned to the least-loaded
    host (by cumulative admitted flops); every later L request follows it.
    That host's pool holds L's warm ``BatchedLatticeRunner`` — the compile
    and tile/K sweeps were paid there, its devices hold the warm dispatch
    shapes — so routing by locality means never re-warming an L on a second
    host while the first sits idle (the serving analog of the paper's
    "work runs where the data was first touched").

    Host-side bookkeeping only; safe under any request mix.
    """

    def __init__(self, n_hosts: int):
        if n_hosts < 1:
            raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
        self.n_hosts = n_hosts
        self._home: dict[int, int] = {}  # L -> host
        self._load: list[float] = [0.0] * n_hosts  # cumulative admitted flops

    def host_for(self, L: int) -> int:
        """The home host for lattice size L (assigned on first sight).

        Assignment charges the chosen host a nominal placement load (one
        multiply's flops, 864·L⁴) immediately — otherwise a burst of
        first-sight Ls with no traffic in between (``SU3Service.warm``
        pre-building several sizes) would all see every host at zero load
        and pile onto host 0, pinning the whole pool there forever.
        """
        if L not in self._home:
            host = min(range(self.n_hosts), key=self._load.__getitem__)
            self._home[L] = host
            self._load[host] += 864.0 * L**4  # nominal placement charge
        return self._home[L]

    def peek(self, L: int) -> int | None:
        """L's home host, or None if L has never been routed."""
        return self._home.get(L)

    def record_load(self, host: int, flops: float) -> None:
        """Charge admitted work to ``host`` (steers future first-sight Ls)."""
        self._load[host] += flops

    def assignments(self) -> dict[int, int]:
        """Snapshot of the sticky (L -> host) table."""
        return dict(self._home)

    def loads(self) -> list[float]:
        return list(self._load)
