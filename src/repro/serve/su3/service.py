"""SU3Service: the plan layer behind a traffic-handling front door.

Composition (everything below the service already exists in the plan layer;
the service adds the queueing discipline and the warm-pool policy):

    submit(a, b, k)                      arun(a, b, k)  [asyncio face]
          │ LocalityRouter — sticky L -> host (work follows the warm data)
          ▼
    per-host DynamicBatcher — (L, k) buckets, warm-size padding, admission
          │  next_batch()  one CoalescedBatch per step()
          ▼
    host-sharded warm pool:
          {(host, L, dtype, layout, tile) -> BatchedLatticeRunner}
          │  each host's runners plan against THAT host's submesh
          │  (MeshSpec.host_submesh) and are built through the persistent
          │  autotune cache: the FIRST request for an (L, dtype) pays
          │  compile + tile/K sweep, every later request hits the warm plan
          ▼
    one vmapped, sharded, (optionally bf16-storage/f32-accumulate) dispatch
          │
          ▼
    split + unpad per request  ->  results keyed by request id

The chain depth ``k`` defaults to the autotuned fused depth for the request's
(backend, L) — ``autotune.tuned_fused_k`` — so callers that don't care get
the measured-best dispatch amortization instead of a hardcoded constant.

Stencil requests (``submit_stencil``) ride the same front door: same
locality router, same per-host batcher (their own by-L queue family), same
warm runner pool.  They coalesce into one vmapped stencil dispatch per
scheduling turn and return canonical vector fields; they never join multiply
dispatches.

Solve requests (``submit_solve``) are the flagship iterative workload: a
CG solve through ``ExecutionPlan.cg_state_init`` / ``cg_iterate`` on one of
two operators — the site-local-adjoint ``(sigma I + S) x = b`` (fused
stencil+axpy iteration), or, for a request with a ``mass``, MILC's even/odd
staggered ``(4 m^2 - D_eo D_oe) x_e = b_e`` on the field the seat prepares.
Each host runs ONE active solve at a time, advanced
``solve_iters_per_step`` CG iterations per scheduling turn — its iteration
count is data-dependent, so it retires the turn its residual crosses tol
(or at ``max_iters``), freeing its seat and queue budget.  When a host has
several kinds pending, turns rotate multiply → stencil → solve so no
sustained stream of one kind starves the others.

A multiply dispatches one coalesced (L, k) bucket per ``step()`` through
one fused-k vmapped call (``BatchedLatticeRunner.multiply``, whose codec
runs on the device).  Requests arriving while a dispatch runs wait for the
next ``step()``.
"""
from __future__ import annotations

import asyncio
import dataclasses
import math
import random
import time
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.chaos.faults import NULL_FAULT_PLAN, FaultPlan, poison_array
from repro.core import autotune
from repro.core.su3.layouts import Layout
from repro.core.su3.plan import (
    CG_DIVERGENCE_FACTOR,
    KS_OPERATOR,
    SITE_LOCAL_OPERATOR,
    BatchedLatticeRunner,
    CGDivergedError,
    EngineConfig,
    site_local,
)
from repro.kernels.su3_stencil import (
    CG_ITER_FLOPS_PER_SITE,
    STENCIL_FLOPS_PER_SITE,
)
from repro.launch.mesh import MeshSpec
from repro.obs.tracer import NULL_TRACER, Tracer
from repro.serve.su3.batcher import (
    BatcherConfig,
    DynamicBatcher,
    LocalityRouter,
    ServeRequest,
)
from repro.serve.su3.metrics import ServiceMetrics, request_flops
from repro.serve.su3.robustness import (
    PRIORITY,
    DeadlineExceededError,
    HostHealth,
    LoadShedError,
    RequestFailure,
    RetriesExhaustedError,
    RetryPolicy,
)
from repro.serve.su3.tenancy import (
    DEFAULT_KIND_SLO,
    DEFAULT_TENANT,
    SLO_BULK,
    SLO_CLASSES,
    SLO_LATENCY,
    AutoscaleConfig,
    BrownoutConfig,
    BrownoutLadder,
    DeficitFairScheduler,
    GroupKey,
    SLOPolicy,
    TenantQuota,
    TokenBucket,
    WarmPoolAutoscaler,
)

DEFAULT_TILE = 128  # small enough that every L >= 2 bucket is a few tiles

# Chrome-trace lane assignment: dispatch spans ride the host's lane so one
# timeline row per host shows the dispatch cadence; request-lifecycle spans
# spread over a block of per-request lanes so overlapping requests don't
# fake nesting in the viewer.
_REQUEST_LANE_BASE = 100
_REQUEST_LANES = 32


def _request_lane(req_id: int) -> int:
    return _REQUEST_LANE_BASE + req_id % _REQUEST_LANES


def _solve_operator(req: ServeRequest) -> str:
    """The CG operator a solve request asks for (``plan.KS_OPERATOR`` when it
    carries a mass)."""
    return SITE_LOCAL_OPERATOR if req.mass is None else KS_OPERATOR


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """The serving tuple: storage/compute dtypes, layout, tuning, batching,
    host topology, and robustness and tenancy policy.

    Attributes:
        dtype: storage dtype of every plan in the pool.
        accum_dtype: ``"float32"`` with ``dtype="bfloat16"`` = bf16-storage /
            f32-accumulate serving plans.
        compression: ``"two_row"`` serves 12-real compressed-gauge plans
            (row 2 reconstructed in-register by every kernel in the pool);
            stacks with the bf16/f32 mixed-precision tuple.
        layout: physical lattice layout (planar-view layouts only).
        autotune: build runner configs through the persistent cache.
        tile: explicit Pallas tile when ``autotune=False`` (0 = DEFAULT_TILE).
        default_k: chain depth when a request leaves k unset; 0 = autotuned.
        batcher: per-host queue discipline (each host gets its own
            DynamicBatcher with this config — admission control is per host).
        cache_directory: autotune cache override (tests).
        hosts: shard the warm pool over this many hosts; each host's runners
            plan on its :meth:`~repro.launch.mesh.MeshSpec.host_submesh` and
            requests route to an L's home host (sticky locality routing).
            On a device pool smaller than the host count, hosts
            oversubscribe the local devices — the routing/batching semantics
            are identical, only physical placement collapses (simulation).
        solve_iters_per_step: CG iterations the host's active solve advances
            per scheduling turn; small values re-open kind rotation (and
            thus multiply/stencil service) more often, large values amortize
            more solver work per turn at the cost of mix latency.
        faults: optional :class:`repro.chaos.FaultPlan` armed over the
            service's injection seams (dispatch / kernel / pool; the halo
            seam lives on the plan).  None = the shared disabled plan —
            every seam is one ``if faults.enabled`` branch, zero cost.
        retry: capped-exponential-backoff retry policy plus the service-wide
            retry budget for failed dispatches.
        default_deadline_s: relative deadline applied to every request that
            does not pass its own (0 = none); a request past its deadline is
            evicted — from the queue OR the active solve seat — and
            completes with a structured ``DeadlineExceededError``.
        quarantine_after: consecutive dispatch failures that latch a host
            out of service (its requests re-seat onto healthy hosts);
            single-host services never self-quarantine.
        numerics_guard: check dispatch outputs for NaN/Inf even with no
            fault plan armed (chaos runs always check).
        slo: per-class policy — deadline defaults and fair-scheduler weights
            for the ``latency`` and ``bulk`` lanes.
        quotas: optional per-tenant :class:`TenantQuota` token buckets
            (``{tenant: TenantQuota}``); a tenant past its bucket is
            rejected at the front door (``submit_*`` returns None, counted
            in ``quota_rejected``).  Tenants absent from the map are
            unmetered.
        autoscale: warm-pool controller; when enabled the service starts at
            ``min_hosts`` active hosts and grows/shrinks the active set
            from queue-depth/occupancy pressure with hysteresis (shrink
            never evicts a seated latency request).  Disabled = every
            configured host stays active (pre-tenancy behavior).
        brownout: optional three-rung overload ladder over the bulk lane
            (None = disabled): rung 1 sheds bulk admissions past a reduced
            queue share, rung 2 additionally degrades bulk solves, rung 3
            rejects new bulk with a Retry-After hint in the LoadShedError.
    """

    dtype: str = "float32"  # storage dtype of every plan in the pool
    accum_dtype: str = ""  # "float32" + dtype="bfloat16" = bf16 serving plans
    compression: str = "none"  # "two_row" = 12-real compressed-gauge plans
    layout: Layout = Layout.SOA
    autotune: bool = True  # build runner configs through the persistent cache
    tile: int = 0  # explicit tile when autotune=False (0 = DEFAULT_TILE)
    default_k: int = 0  # chain depth when a request leaves k unset; 0 = tuned
    batcher: BatcherConfig = dataclasses.field(default_factory=BatcherConfig)
    cache_directory: str | None = None  # autotune cache override (tests)
    hosts: int = 1  # shard the warm pool across this many hosts
    solve_iters_per_step: int = 4  # CG iterations per solve scheduling turn
    faults: FaultPlan | None = None  # chaos plan armed over the serve seams
    retry: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    default_deadline_s: float = 0.0  # relative per-request deadline (0 = none)
    quarantine_after: int = 3  # consecutive failures latching a host out
    numerics_guard: bool = False  # NaN/Inf-check outputs without a fault plan
    slo: SLOPolicy = dataclasses.field(default_factory=SLOPolicy)
    quotas: Any = None  # {tenant: TenantQuota} token buckets (None = unmetered)
    autoscale: AutoscaleConfig = dataclasses.field(default_factory=AutoscaleConfig)
    brownout: BrownoutConfig | None = None  # overload ladder (None = disabled)

    def __post_init__(self) -> None:
        # the pool serves the planar Pallas kernel; AOS has no planar view,
        # so reject it here instead of inside the first user request
        if Layout(self.layout) not in (Layout.SOA, Layout.AOSOA):
            raise ValueError(
                f"serving pool requires a planar-view layout (soa/aosoa), "
                f"got {Layout(self.layout).value!r}"
            )
        # best_config sweeps (and cache-keys) SoA plans only — applying its
        # tile/fused_k to another layout would serve never-measured numbers
        # under a mislabeled cache entry
        if self.autotune and Layout(self.layout) != Layout.SOA:
            raise ValueError(
                "the autotune cache tunes SoA plans only; serve "
                f"{Layout(self.layout).value!r} with autotune=False and an "
                "explicit tile"
            )
        if self.hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {self.hosts}")
        if self.solve_iters_per_step < 1:
            raise ValueError(
                f"solve_iters_per_step must be >= 1, got "
                f"{self.solve_iters_per_step}"
            )
        if self.default_deadline_s < 0:
            raise ValueError(
                f"default_deadline_s must be >= 0, got {self.default_deadline_s}"
            )
        if self.quarantine_after < 1:
            raise ValueError(
                f"quarantine_after must be >= 1, got {self.quarantine_after}"
            )
        if self.quotas is not None:
            for tenant, quota in dict(self.quotas).items():
                if not tenant or not isinstance(tenant, str):
                    raise ValueError(
                        f"quota tenants must be non-empty strings, got "
                        f"{tenant!r}"
                    )
                if not isinstance(quota, TenantQuota):
                    raise ValueError(
                        f"quotas values must be TenantQuota, got "
                        f"{type(quota).__name__} for tenant {tenant!r}"
                    )
        if self.autoscale.enabled and self.autoscale.min_hosts > self.hosts:
            raise ValueError(
                f"autoscale.min_hosts={self.autoscale.min_hosts} exceeds "
                f"hosts={self.hosts}"
            )


def _host_stack(xs: list[Any], pad: int) -> np.ndarray:
    """Canonical request arrays stacked on the host, with ``pad`` zero
    entries appended (canonical arrays never go onto an accelerator)."""
    a = np.stack([np.asarray(x) for x in xs])
    if pad:
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
    return a


class SU3Service:
    """Dynamic-batching SU3 lattice serving over a warm ExecutionPlan pool.

    Args:
        cfg: the :class:`ServiceConfig` serving tuple.
        mesh: optional explicit mesh every runner plans against (single-host
            only; mutually exclusive with ``cfg.hosts > 1``, where each
            host's runners plan on their own submesh).
        tracer: optional :class:`repro.obs.Tracer` recording the request
            lifecycle (admit → queue wait → seat → dispatch → complete) and
            per-dispatch spans.  Defaults to the shared disabled tracer —
            every instrumentation site is one ``if tracer.enabled`` branch,
            so untraced serving allocates nothing.
    """

    def __init__(self, cfg: ServiceConfig | None = None, mesh: Any = None,
                 tracer: Tracer | None = None):
        self.cfg = cfg if cfg is not None else ServiceConfig()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if self.cfg.hosts > 1 and mesh is not None:
            raise ValueError(
                "pass EITHER an explicit mesh (single-host pool) OR "
                "hosts > 1 (per-host submeshes derived from MeshSpec)"
            )
        self.mesh = mesh
        self.mesh_spec = MeshSpec(hosts=self.cfg.hosts)
        self.router = LocalityRouter(self.cfg.hosts)
        self._batchers = [
            DynamicBatcher(self.cfg.batcher) for _ in range(self.cfg.hosts)
        ]
        self.batcher = self._batchers[0]  # host 0; aggregate depth: queued()
        self.metrics = ServiceMetrics()
        self._pool: dict[tuple, BatchedLatticeRunner] = {}
        self._ecfg: dict[int, EngineConfig] = {}  # L -> resolved plan tuple
        self._tuned_k: dict[int, int] = {}
        self._results: dict[int, jax.Array] = {}
        # (host, L, dtype, layout, tile) -> jitted vmapped stencil dispatch
        self._stencil_steps: dict[tuple, Any] = {}
        # (host, group) kind fairness: the kind that group's LAST turn on
        # the host served; the next turn serves the first pending kind
        # strictly after it in the multiply -> stencil -> solve rotation,
        # so within one (tenant, class) group no sustained stream of one
        # kind starves the others.  WHICH group owns a turn is the deficit
        # fair scheduler's call (replacing the old global kind rotation).
        self._last_kind: dict[tuple[int, GroupKey], str] = {}
        self._sched = DeficitFairScheduler(weight_for=self.cfg.slo.weight_for)
        # per-host active solve: ONE data-dependent CG solve advanced a few
        # iterations per scheduling turn (kind="solve" seat)
        self._solves: dict[int, dict[str, Any]] = {}
        self._awaited: set[int] = set()  # ids owned by pending arun callers
        self._seen_shapes: set[tuple] = set()
        self._next_id = 0
        self._rr_host = 0  # round-robin cursor over hosts for step()
        # -- robustness state (ISSUE 9) ---------------------------------------
        self.faults = self.cfg.faults if self.cfg.faults is not None \
            else NULL_FAULT_PLAN
        self.health = HostHealth(self.cfg.hosts, self.cfg.quarantine_after)
        self._retry_rng = random.Random(self.cfg.retry.seed)
        self._retry_budget = self.cfg.retry.budget
        # requests waiting out a backoff: (eligible perf_counter s, request)
        self._retry_q: list[tuple[float, ServeRequest]] = []
        # set the first time any request carries a deadline, so the
        # deadline-free hot path never scans queues/seats for expiry
        self._deadlines_armed = bool(
            self.cfg.default_deadline_s
            or self.cfg.slo.latency_deadline_s
            or self.cfg.slo.bulk_deadline_s)
        # -- tenancy state (ISSUE 10) ------------------------------------------
        self._quota_buckets: dict[str, TokenBucket] = {}
        self._brownout = BrownoutLadder(self.cfg.brownout) \
            if self.cfg.brownout is not None else None
        if self.cfg.autoscale.enabled:
            self._autoscaler: WarmPoolAutoscaler | None = WarmPoolAutoscaler(
                self.cfg.autoscale, self.cfg.hosts)
            self._active_hosts = self.cfg.autoscale.min_hosts
        else:
            self._autoscaler = None
            self._active_hosts = self.cfg.hosts
        self.metrics.active_hosts = self._active_hosts

    # -- warm pool -----------------------------------------------------------

    def _engine_config(self, L: int) -> EngineConfig:
        """Resolved plan tuple for L, memoized — the autotune path otherwise
        re-reads the JSON cache file on every dispatch."""
        if L not in self._ecfg:
            cfg = self.cfg
            if cfg.autotune:
                self._ecfg[L] = autotune.tuned_engine_config(
                    L=L, dtype=cfg.dtype, cache_directory=cfg.cache_directory,
                    layout=cfg.layout, accum_dtype=cfg.accum_dtype,
                    compression=cfg.compression,
                )
            else:
                self._ecfg[L] = EngineConfig(
                    L=L, dtype=cfg.dtype, layout=cfg.layout,
                    tile=cfg.tile or DEFAULT_TILE, accum_dtype=cfg.accum_dtype,
                    compression=cfg.compression,
                )
        return self._ecfg[L]

    def _host_mesh(self, host: int) -> Any:
        """The mesh host ``host``'s runners plan against."""
        if self.cfg.hosts == 1:
            return self.mesh  # explicit mesh or None (all local devices)
        return self.mesh_spec.host_submesh(host)

    def runner_for(self, L: int, host: int | None = None) -> BatchedLatticeRunner:
        """The warm runner for lattice size L on its home host.

        Args:
            L: lattice extent (requests carry L**4 sites).
            host: explicit host override; default = the router's sticky
                home for L (assigned least-loaded-first on first sight).

        Returns:
            The host-local :class:`BatchedLatticeRunner` (built + autotuned
            on first use; warm afterwards).
        """
        if host is None:
            host = self._home(L)
        ecfg = self._engine_config(L)
        key = (host, L, ecfg.dtype, ecfg.layout.value, ecfg.tile, ecfg.compression)
        runner = self._pool.get(key)
        if runner is None:
            if self.faults.enabled:
                # "pool" seam: warm-runner construction fails (a host that
                # cannot compile/allocate its plan).  The build is retried
                # immediately — charged as one retry — and repeated cold-
                # build failures walk the host toward quarantine.
                f = self.faults.ask("pool", host=host, L=L)
                if f is not None:
                    self.metrics.record_fault()
                    self.metrics.record_retry()
                    if self.tracer.enabled:
                        self.tracer.event("chaos.fault", lane=host,
                                          site="pool", action=f.action,
                                          seq=f.seq, host=host, L=L)
                    if self.health.record_failure(host, "pool-build"):
                        self._quarantine(host)
            runner = BatchedLatticeRunner(ecfg, self._host_mesh(host))
            self._pool[key] = runner
        return runner

    def _serving_hosts(self) -> list[int]:
        """Hosts eligible for new work: active (autoscaler set) and not
        quarantined.  Never empty — if quarantine has eaten the whole
        active set, the healthy hosts beyond it serve (HostHealth never
        quarantines the last healthy host)."""
        hosts = [
            h for h in range(self._active_hosts)
            if not self.health.is_quarantined(h)
        ]
        return hosts or self.health.healthy_hosts()

    def _home(self, L: int) -> int:
        """The lattice size's home host, re-homed deterministically onto a
        serving host when the sticky assignment is quarantined or scaled
        out of the active pool."""
        host = self.router.host_for(L)
        serving = self._serving_hosts()
        if host not in serving:
            host = serving[L % len(serving)]
        return host

    def pool_keys(self) -> list[tuple]:
        """Sorted warm-pool keys:
        ``(host, L, dtype, layout, tile, compression)``."""
        return sorted(self._pool)

    def default_k_for(self, L: int) -> int:
        """Request chain depth when unspecified: configured or autotuned."""
        if self.cfg.default_k:
            return self.cfg.default_k
        if not self.cfg.autotune:
            return 1
        if L not in self._tuned_k:
            self._tuned_k[L] = autotune.tuned_fused_k(
                L=L, dtype=self.cfg.dtype, accum_dtype=self.cfg.accum_dtype,
                compression=self.cfg.compression,
                cache_directory=self.cfg.cache_directory,
            )
        return self._tuned_k[L]

    def warm(self, Ls: tuple[int, ...], ks: tuple[int, ...] = (1,),
             batch_sizes: tuple[int, ...] = (), stencil: bool = False) -> None:
        """Pre-build runners (and optionally compile dispatch shapes).

        Serving cold-start control: first-touch compiles happen here instead
        of inside a user request's latency.  ``stencil=True`` additionally
        compiles the vmapped stencil dispatch at each warm batch size.
        """
        for L in Ls:
            runner = self.runner_for(L)
            n_sites = L**4
            for bsz in batch_sizes:
                a = np.zeros((bsz, n_sites, 4, 3, 3), np.complex64)
                b = np.zeros((bsz, 4, 3, 3), np.complex64)
                for k in ks:
                    runner.multiply(a, b, k=k).block_until_ready()
                    self._seen_shapes.add(self._shape_key(runner, L, k, bsz))
                if stencil:
                    host = self.router.host_for(L)
                    dispatched = bsz + (-bsz) % runner.n_devices
                    u_w = np.zeros((dispatched, n_sites, 4, 3, 3), np.complex64)
                    v = np.zeros((dispatched, n_sites, 3), np.complex64)
                    u_phys = runner.pack_batch(u_w)
                    v_p = runner.pack_vec_batch(v)
                    step = self._stencil_step_for(runner, host, L)
                    step(u_phys, v_p).block_until_ready()
                    self._seen_shapes.add(("stencil", L, dispatched))

    @staticmethod
    def _shape_key(runner: BatchedLatticeRunner, L: int, k: int, bsz: int) -> tuple:
        """Compiled-shape identity: the runner pads the batch up to a device
        multiple, so that post-pad size — not the request count — is what
        the jit cache keys on."""
        return (L, k, bsz + (-bsz) % runner.n_devices)

    # -- tracing -------------------------------------------------------------

    def _dispatch_span(self, runner: BatchedLatticeRunner, host: int,
                       kind: str, L: int, k: int, mode: str, live: int,
                       padded: int, flops: float, cold: bool):
        """A live ``dispatch`` span around one timed dispatch block (the
        shared no-op span, with no attrs built, while tracing is off)."""
        if not self.tracer.enabled:
            return NULL_TRACER.span("dispatch")
        ecfg = runner.cfg
        return self.tracer.span(
            "dispatch", lane=host,
            kind=kind, mode=mode, host=host, L=L, k=k,
            tile=ecfg.tile, dtype=ecfg.dtype, compression=ecfg.compression,
            live=live, padded=padded, flops=flops, cold=cold)

    @staticmethod
    def _seat_popped(reqs: list[ServeRequest]) -> None:
        """A queued request is seated when its batch is popped: the queue
        wait ends there, before the batch is stacked on the host."""
        now = time.perf_counter()
        for r in reqs:
            r.seated_s = now

    def _trace_request(self, req: ServeRequest, done_s: float, host: int,
                       mode: str) -> None:
        """Whole-lifecycle span for one completed request: admission →
        completion, with the queue wait (admit → first seat) as an attr."""
        seated = req.seated_s or req.arrival_s
        self.tracer.add_span(
            "request", req.arrival_s, done_s, lane=_request_lane(req.req_id),
            req_id=req.req_id, kind=req.kind, L=req.L, k=req.k, host=host,
            mode=mode, queue_wait_s=seated - req.arrival_s)

    # -- request intake ------------------------------------------------------

    @staticmethod
    def _infer_L(a: jax.Array) -> int:
        n_sites = a.shape[0]
        L = round(n_sites ** 0.25)
        if L**4 != n_sites or a.shape[1:] != (4, 3, 3):
            raise ValueError(
                f"request lattice must be (L**4, 4, 3, 3) canonical complex, "
                f"got {a.shape}"
            )
        return L

    def queued(self) -> int:
        """Total waiting requests across every host's batcher."""
        return sum(len(b) for b in self._batchers)

    def _deadline(self, deadline_s: float | None, arrival_s: float,
                  slo: str = SLO_BULK) -> float:
        """Absolute deadline for a request: its own relative deadline, else
        the SLO class's default, else the service-wide default, else none
        (0.0)."""
        d = deadline_s
        if d is None:
            d = self.cfg.slo.deadline_for(slo) or self.cfg.default_deadline_s
        if d and d > 0:
            self._deadlines_armed = True
            return arrival_s + d
        return 0.0

    @staticmethod
    def _resolve_slo(kind: str, slo: str | None) -> str:
        """The request's SLO class: explicit, else the kind's default."""
        if slo is None:
            return DEFAULT_KIND_SLO[kind]
        if slo not in SLO_CLASSES:
            raise ValueError(
                f"slo must be one of {SLO_CLASSES}, got {slo!r}"
            )
        return slo

    @staticmethod
    def _check_tenant(tenant: str) -> str:
        if not tenant or not isinstance(tenant, str):
            raise ValueError(
                f"tenant must be a non-empty string, got {tenant!r}"
            )
        return tenant

    def _quota_admit(self, tenant: str, now: float) -> bool:
        """Charge the tenant's token bucket; False = quota backpressure
        (the submit returns None before touching any queue)."""
        quotas = self.cfg.quotas
        if not quotas:
            return True
        spec = quotas.get(tenant)
        if spec is None:
            return True
        bucket = self._quota_buckets.get(tenant)
        if bucket is None:
            bucket = self._quota_buckets[tenant] = TokenBucket(spec)
        if bucket.try_take(now):
            return True
        self.metrics.record_quota_reject(tenant)
        if self.tracer.enabled:
            self.tracer.event("quota.reject", lane=0, tenant=tenant)
        return False

    def _brownout_door(self, req: ServeRequest, host: int) -> int | None:
        """The brownout ladder's bulk-lane admission check.  Returns the
        request id when the ladder SHED the arrival (the id resolves
        immediately to a LoadShedError — zero-lost accounting holds, the
        caller can pop the structured error), or None to admit normally.
        Latency-class requests are never browned out."""
        ladder = self._brownout
        if ladder is None or ladder.rung < 1 or req.slo != SLO_BULK:
            return None
        rung = ladder.rung
        retry_after = 0.0
        if rung >= 3:
            retry_after = self.cfg.brownout.retry_after_s
        else:
            # rung 1/2: bulk keeps only a reduced share of the queue budget
            budget = max(1, int(self.cfg.batcher.max_queue_depth
                                * self.cfg.brownout.bulk_queue_fraction))
            if self._batchers[host].depth_for_slo(SLO_BULK) < budget:
                return None
        self._next_id += 1
        self.metrics.record_shed(req.kind, for_kind="brownout",
                                 tenant=req.tenant, slo=req.slo)
        self._results[req.req_id] = LoadShedError(
            req_id=req.req_id, kind=req.kind, priority=req.priority,
            shed_for_kind="brownout", attempts=req.attempts,
            retry_after_s=retry_after)
        if self.tracer.enabled:
            self.tracer.event(
                "brownout.shed", lane=_request_lane(req.req_id),
                req_id=req.req_id, kind=req.kind, tenant=req.tenant,
                rung=rung, retry_after_s=retry_after)
        return req.req_id

    def _shed(self, victim: ServeRequest, for_kind: str) -> None:
        """Deliver a structured LoadShedError to a queue victim evicted to
        admit a higher-priority arrival."""
        self.metrics.record_shed(victim.kind, for_kind=for_kind,
                                 tenant=victim.tenant, slo=victim.slo)
        self._results[victim.req_id] = LoadShedError(
            req_id=victim.req_id, kind=victim.kind, priority=victim.priority,
            shed_for_kind=for_kind, attempts=victim.attempts)
        if self.tracer.enabled:
            self.tracer.event(
                "shed", lane=_request_lane(victim.req_id),
                req_id=victim.req_id, kind=victim.kind)

    def _admit(self, req: ServeRequest, host: int, load_flops: float,
               depth: int) -> int | None:
        """Shared admission tail: queue-budget check with priority-aware
        shedding (the youngest strictly-lower-priority BULK-class request
        is evicted — with a structured error — to admit a latency-sensitive
        arrival; the latency lane is never shed), then load/metrics/trace
        accounting."""
        batcher = self._batchers[host]
        if not batcher.submit(req):
            victim = batcher.shed_lowest(req.priority, sheddable_slo=SLO_BULK)
            if victim is not None:
                self._shed(victim, req.kind)
            if victim is None or not batcher.submit(req):
                self.metrics.record_reject(req.kind)
                return None
        self.router.record_load(host, load_flops)
        self._next_id += 1
        self.metrics.record_admit(depth + 1, tenant=req.tenant, slo=req.slo)
        if self.tracer.enabled:
            self.tracer.event(
                "admit", lane=_request_lane(req.req_id), req_id=req.req_id,
                kind=req.kind, L=req.L, k=req.k, host=host, tenant=req.tenant,
                slo=req.slo, queue_depth=depth + 1)
        return req.req_id

    def submit(self, a: jax.Array, b: jax.Array, k: int | None = None,
               deadline_s: float | None = None,
               tenant: str = DEFAULT_TENANT,
               slo: str | None = None) -> int | None:
        """Queue one lattice multiply on its home host's batcher.

        Args:
            a: canonical complex lattice ``(L**4, 4, 3, 3)``.
            b: canonical complex link matrix set ``(4, 3, 3)``.
            k: chain depth (``C = A⊗B`` applied k times); None = the
                autotuned default for (backend, L).
            deadline_s: relative deadline; None = the SLO class default,
                else the configured service default.  A request past its
                deadline is evicted wherever it sits and completes with a
                structured ``DeadlineExceededError``.
            tenant: tenant identity (quota metering + fairness group);
                every pre-tenancy call site rides the default tenant.
            slo: SLO class ("latency"/"bulk"); None = the kind's default
                (multiplies are bulk).

        Returns:
            A request id, or None when the tenant's quota bucket is dry or
            the home host's queue budget is exhausted (backpressure —
            caller retries later) and nothing lower-priority could be shed
            to make room.  Under brownout the id may resolve immediately
            to a ``LoadShedError`` carrying a Retry-After hint.
        """
        L = self._infer_L(a)
        tenant = self._check_tenant(tenant)
        slo = self._resolve_slo("multiply", slo)
        host = self._home(L)
        depth = self.queued()
        arrival = time.perf_counter()
        if not self._quota_admit(tenant, arrival):
            return None
        req = ServeRequest(
            req_id=self._next_id, a=a, b=b, L=L,
            k=k if k is not None else self.default_k_for(L),
            arrival_s=arrival,
            deadline_s=self._deadline(deadline_s, arrival, slo),
            priority=PRIORITY["multiply"], tenant=tenant, slo=slo,
        )
        shed_id = self._brownout_door(req, host)
        if shed_id is not None:
            return shed_id
        return self._admit(req, host, request_flops(req.n_sites, req.k), depth)

    def submit_stencil(self, u: jax.Array, v: jax.Array,
                       deadline_s: float | None = None,
                       tenant: str = DEFAULT_TENANT,
                       slo: str | None = None) -> int | None:
        """Queue one nearest-neighbor stencil application on its home host.

        Args:
            u: canonical complex gauge lattice ``(L**4, 4, 3, 3)``.
            v: canonical complex color-vector field ``(L**4, 3)``.

        Returns:
            A request id (result: the canonical ``(L**4, 3)`` output vector
            field), or None under backpressure — same contract as
            :meth:`submit`.  Stencil requests ride the SAME warm pool,
            locality router, and per-host batcher as multiplies; they
            coalesce by lattice size into one vmapped stencil dispatch and
            never join a multiply dispatch (their output is a vector field).
        """
        L = self._infer_L(u)
        if v.shape != (L**4, 3):
            raise ValueError(
                f"stencil vector field must be (L**4, 3) canonical complex "
                f"matching the lattice, got {v.shape} for L={L}"
            )
        tenant = self._check_tenant(tenant)
        slo = self._resolve_slo("stencil", slo)
        host = self._home(L)
        depth = self.queued()
        arrival = time.perf_counter()
        if not self._quota_admit(tenant, arrival):
            return None
        req = ServeRequest(
            req_id=self._next_id, a=u, b=v, L=L, k=1,
            arrival_s=arrival, kind="stencil",
            deadline_s=self._deadline(deadline_s, arrival, slo),
            priority=PRIORITY["stencil"], tenant=tenant, slo=slo,
        )
        shed_id = self._brownout_door(req, host)
        if shed_id is not None:
            return shed_id
        return self._admit(
            req, host, float(STENCIL_FLOPS_PER_SITE) * req.n_sites, depth)

    def submit_solve(self, u: jax.Array, b: jax.Array, tol: float = 1e-6,
                     max_iters: int = 200,
                     deadline_s: float | None = None,
                     tenant: str = DEFAULT_TENANT,
                     slo: str | None = None,
                     mass: float | None = None) -> int | None:
        """Queue one CG solve on its home host, on one of two operators:

        * ``mass=None``: the site-local-adjoint operator
          ``(sigma I + S) x = b`` (sigma = ``plan.CG_SHIFT``; S Hermitian
          only on fields constant along each link's own direction);
        * ``mass=m``: MILC's even/odd staggered operator
          ``(4 m^2 - D_eo D_oe) x_e = b_e`` (phases, antiperiodic t, the
          neighbour's backward link), on the field the seat prepares.

        Args:
            u: canonical complex gauge lattice ``(L**4, 4, 3, 3)``.
            b: canonical complex right-hand side ``(L**4, 3)``, or the
                even-site ``(L**4 // 2, 3)`` (``layouts.parity_sites``
                order) with a ``mass``.
            tol: relative-residual target ``||r|| <= tol ||b||``.
            max_iters: iteration cap; the request retires (best iterate
                delivered) rather than spinning past it.
            mass: the staggered operator's valence mass (> 0).

        Returns:
            A request id (result: the canonical solution, shaped like
            ``b``), or None under backpressure — same contract as
            :meth:`submit`.  The solve rides the SAME warm pool, locality
            router, and per-host batcher; its iteration count is
            data-dependent, so it advances ``solve_iters_per_step`` CG
            iterations per scheduling turn through the fused stencil+axpy
            kernel and retires the turn its residual crosses tol.
        """
        L = self._infer_L(u)
        sites = L**4 if mass is None else L**4 // 2
        if b.shape != (sites, 3):
            raise ValueError(
                f"solve right-hand side must be ({sites}, 3) canonical complex "
                f"matching the lattice (its even sites with a mass), got "
                f"{b.shape} for L={L}"
            )
        if mass is not None and not (mass > 0 and L % 2 == 0):
            raise ValueError(f"a staggered solve needs mass > 0 and an even L, "
                             f"got mass={mass}, L={L}")
        if tol < 0:
            raise ValueError(f"tol must be >= 0, got {tol}")
        if max_iters < 1:
            raise ValueError(f"max_iters must be >= 1, got {max_iters}")
        tenant = self._check_tenant(tenant)
        slo = self._resolve_slo("solve", slo)
        host = self._home(L)
        depth = self.queued()
        arrival = time.perf_counter()
        if not self._quota_admit(tenant, arrival):
            return None
        req = ServeRequest(
            req_id=self._next_id, a=u, b=b, L=L, k=1,
            arrival_s=arrival, kind="solve",
            tol=tol, max_iters=max_iters, mass=mass,
            deadline_s=self._deadline(deadline_s, arrival, slo),
            priority=PRIORITY["solve"], tenant=tenant, slo=slo,
        )
        shed_id = self._brownout_door(req, host)
        if shed_id is not None:
            return shed_id
        # nominal admission charge: a typical shifted-CG iteration count;
        # the true data-dependent bill is charged per dispatched chunk
        return self._admit(
            req, host, float(CG_ITER_FLOPS_PER_SITE) * req.n_sites * 10, depth)

    # -- dispatch ------------------------------------------------------------

    def _work_pending(self) -> bool:
        return bool(self._retry_q) or self._work_ready()

    def _work_ready(self) -> bool:
        """True while a turn has something to dispatch: a queued request or
        an active solve (retry backoffs aside)."""
        return bool(self._solves) or any(len(b) for b in self._batchers)

    def pending(self) -> bool:
        """True while any request waits in a queue, a retry backoff, or an
        active solve — the loop condition for callers that step the
        service themselves."""
        return self._work_pending()

    # -- failure lifecycle (ISSUE 9) ------------------------------------------

    @staticmethod
    def _finite(x: jax.Array) -> bool:
        return bool(jax.device_get(jnp.all(jnp.isfinite(x))))

    def _fail(self, req: ServeRequest, err: Exception) -> None:
        """Deliver a structured failure through the result channel: a
        stepping caller gets the exception object from ``pop_result``, an
        ``arun`` caller gets it raised."""
        self._results[req.req_id] = err

    def _timeout(self, req: ServeRequest, now: float,
                 partial: Any = None) -> None:
        self.metrics.record_timeout(req.kind, tenant=req.tenant, slo=req.slo)
        self._fail(req, DeadlineExceededError(
            req_id=req.req_id, kind=req.kind,
            deadline_s=req.deadline_s - req.arrival_s,
            waited_s=now - req.arrival_s, attempts=req.attempts,
            partial=partial))
        if self.tracer.enabled:
            self.tracer.event(
                "timeout", lane=_request_lane(req.req_id), req_id=req.req_id,
                kind=req.kind, waited_s=now - req.arrival_s)

    def _retry_or_fail(self, req: ServeRequest, cause: str,
                       terminal: Exception | None = None) -> bool:
        """Charge one failed attempt: requeue with capped-exponential
        backoff while the per-request cap and the service-wide retry budget
        allow, else deliver the terminal structured error.  Returns True
        when the request was requeued."""
        req.attempts += 1
        policy = self.cfg.retry
        if req.attempts <= policy.max_retries and self._retry_budget > 0:
            self._retry_budget -= 1
            self.metrics.record_retry()
            delay = policy.backoff_s(req.attempts, self._retry_rng)
            self._retry_q.append((time.perf_counter() + delay, req))
            if self.tracer.enabled:
                self.tracer.event(
                    "retry", lane=_request_lane(req.req_id),
                    req_id=req.req_id, attempt=req.attempts, cause=cause,
                    backoff_s=delay)
            return True
        self.metrics.record_retries_exhausted()
        if terminal is None:
            terminal = RetriesExhaustedError(
                req_id=req.req_id, kind=req.kind, attempts=req.attempts,
                cause=cause,
                budget_exhausted=(self._retry_budget <= 0
                                  and req.attempts <= policy.max_retries))
        self._fail(req, terminal)
        return False

    def _drain_retry_queue(self, now: float) -> None:
        """Move backoff-expired requests back into their (healthy) home
        host's queue; a still-full queue waits another beat rather than
        dropping the request (the deadline sweep bounds that wait)."""
        still: list[tuple[float, ServeRequest]] = []
        for eligible_s, req in self._retry_q:
            if eligible_s > now:
                still.append((eligible_s, req))
            elif not self._batchers[self._home(req.L)].submit(req):
                still.append((now + self.cfg.retry.base_s, req))
        self._retry_q = still

    def _evict_expired(self, now: float) -> None:
        """The deadline sweep: evict every expired request wherever it sits
        — queued, waiting out a backoff, or the active solve — and deliver
        structured timeouts.  A freed solve seat is filled on the host's
        next solve turn."""
        for batcher in self._batchers:
            for req in batcher.evict_expired(now):
                self._timeout(req, now)
        if self._retry_q:
            keep = []
            for eligible_s, req in self._retry_q:
                if req.deadline_s and req.deadline_s <= now:
                    self._timeout(req, now)
                else:
                    keep.append((eligible_s, req))
            self._retry_q = keep
        for host in list(self._solves):
            active = self._solves[host]
            req = active["req"]
            if req.deadline_s and req.deadline_s <= now:
                # best iterate so far rides out as the timeout's partial
                partial = active["unpack"](active["state"]["x"])
                del self._solves[host]
                self._timeout(req, now, partial=partial)

    def _drain_host(self, host: int) -> list[ServeRequest]:
        """Pull every request ``host`` holds — queued, and the active solve —
        off the host (the solve's progress is discarded; the re-run is
        deterministic).  Shared by the quarantine and scale-down paths."""
        moved: list[ServeRequest] = list(self._batchers[host].drain())
        active = self._solves.pop(host, None)
        if active is not None:
            moved.append(active["req"])
        return moved

    def _reseat(self, moved: list[ServeRequest], cause: str) -> int:
        """Re-seat displaced requests onto serving hosts; returns the count
        that landed.  A request whose deadline has ALREADY passed resolves
        as a DeadlineExceededError right here — exactly once — instead of
        being resubmitted only for the next sweep to evict it (the
        deadline-expiry x re-seat race).  Re-seats that bounce off a full
        queue fail structurally."""
        now = time.perf_counter()
        reseated = 0
        for req in moved:
            if req.deadline_s and req.deadline_s <= now:
                self._timeout(req, now)
                continue
            target = self._home(req.L)
            if self._batchers[target].submit(req):
                reseated += 1
            else:
                self.metrics.record_retries_exhausted()
                self._fail(req, RetriesExhaustedError(
                    req_id=req.req_id, kind=req.kind, attempts=req.attempts,
                    cause=cause))
        return reseated

    def _quarantine(self, host: int) -> None:
        """Last rung of the degradation ladder: the health tracker latched
        ``host`` out.  Every request it holds re-seats onto a healthy host
        via :meth:`_reseat` (``_home`` already excludes the latched host)."""
        moved = self._drain_host(host)
        reseated = self._reseat(
            moved, "quarantine re-seat rejected under backpressure")
        self.metrics.record_quarantine(reseated=reseated)
        if self.tracer.enabled:
            self.tracer.event(
                "chaos.quarantine", lane=host, host=host, reseated=reseated,
                cause=self.health.last_cause[host])

    def _dispatch_fault(self, host: int, kind: str, mode: str):
        """Consult the ``dispatch`` seam.  Returns the Fault only for the
        "fail" action (the caller runs its failure path); "delay" is applied
        here — a stalled-rank injection, the launch still runs."""
        f = self.faults.ask("dispatch", host=host, kind=kind, mode=mode)
        if f is None:
            return None
        self.metrics.record_fault()
        if self.tracer.enabled:
            self.tracer.event(
                "chaos.fault", lane=host, site="dispatch", action=f.action,
                seq=f.seq, host=host, kind=kind, mode=mode)
        if f.action == "delay":
            time.sleep(f.delay_s)
            return None
        return f

    def _poison_output(self, x: jax.Array, host: int, kind: str) -> jax.Array:
        """Consult the ``kernel`` seam; a fired fault poisons the dispatch
        output with NaN/Inf for the finiteness guard to catch."""
        f = self.faults.ask("kernel", host=host, kind=kind)
        if f is None:
            return x
        self.metrics.record_fault()
        if self.tracer.enabled:
            self.tracer.event(
                "chaos.fault", lane=host, site="kernel", action=f.action,
                seq=f.seq, host=host, kind=kind)
        return poison_array(x, f.action)

    def _seated_latency(self, host: int) -> bool:
        """True when ``host``'s active solve is latency-class (a shrink must
        never evict one — the veto the autoscaler docs promise)."""
        active = self._solves.get(host)
        return active is not None and active["req"].slo == SLO_LATENCY

    def _scale_down(self) -> None:
        """Retire the top active host: drain its queued/seated work onto the
        remaining hosts (the quarantine re-seat machinery).  Vetoed when the
        victim holds a seated latency request — the controller proposes
        again after its next cold streak."""
        victim = self._active_hosts - 1
        if self._seated_latency(victim):
            if self.tracer.enabled:
                self.tracer.event("scale.veto", lane=victim, host=victim)
            return
        self._active_hosts -= 1  # _home() now excludes the victim
        moved = self._drain_host(victim)
        reseated = self._reseat(
            moved, "scale-down re-seat rejected under backpressure")
        self.metrics.record_scale(-1, self._active_hosts)
        if self.tracer.enabled:
            self.tracer.event(
                "scale.down", lane=victim, host=victim,
                active=self._active_hosts, reseated=reseated)

    def _observe_pressure(self) -> None:
        """One control-loop sample per step(): feed the brownout ladder and
        the warm-pool autoscaler the same load signals — queued fraction of
        the active queue budget, blended with seat occupancy while a
        backlog exists — and apply their decisions.  Both controllers are
        functions of the observation SEQUENCE, so a same-seed replay of the
        same traffic reproduces every transition and scale event."""
        active = self._serving_hosts()
        n = max(1, len(active))
        depth = self.queued()
        cap = max(1, self.cfg.batcher.max_queue_depth) * n
        # a host's one live seat is its active solve
        occupancy = sum(1 for h in active if h in self._solves) / n
        pressure = min(1.0, depth / cap)
        if depth:
            pressure = max(pressure, occupancy)
        if self._brownout is not None:
            new_rung = self._brownout.observe(pressure)
            self.metrics.record_brownout_turn(self._brownout.rung)
            if new_rung is not None:
                self.metrics.record_brownout_transition(new_rung)
                if self.tracer.enabled:
                    t = self._brownout.transitions[-1]
                    self.tracer.event(
                        "brownout.transition", lane=0, rung=new_rung,
                        from_rung=t["from"], pressure=t["pressure"])
        if self._autoscaler is not None:
            delta = self._autoscaler.observe(
                depth_per_host=depth / n, occupancy=occupancy,
                active=self._active_hosts)
            if delta > 0:
                self._active_hosts += 1
                self.metrics.record_scale(+1, self._active_hosts)
                if self.tracer.enabled:
                    self.tracer.event(
                        "scale.up", lane=0, active=self._active_hosts)
            elif delta < 0:
                self._scale_down()

    def step(self) -> int:
        """Advance the service by one scheduling turn; returns completed
        request count.

        Turn ownership is two-level.  The deficit-weighted fair scheduler
        first picks WHICH (tenant, SLO class) group owns the turn on the
        next host with pending work — every pending group accrues
        weight-proportional credit, so a backlogged bulk tenant cannot
        monopolize turns and a pending latency group is served within a
        provable bound (tests/test_tenancy.py pins it).  Within the granted
        group, kinds rotate multiply -> stencil -> solve exactly as before
        — per (host, group) now — so no sustained stream of one kind
        starves the others *inside* a group.  A multiply turn serves one
        coalesced (L, k) bucket from the group's buckets.  Each step also
        feeds one pressure sample to the brownout ladder and the warm-pool
        autoscaler (when configured).

        A traced turn with work to dispatch is a live ``serve.step`` span:
        the host work between two dispatches (delivering results, stacking,
        packing) then lies under one span, which a profile can name the
        device's idle time by.  Empty turns (retry backoffs, idle polling)
        record none, so they cannot crowd the flight recorder.
        """
        now = time.perf_counter()
        if self._retry_q:
            self._drain_retry_queue(now)
        if self._deadlines_armed:
            self._evict_expired(now)
        if self._brownout is not None or self._autoscaler is not None:
            self._observe_pressure()
        if self.tracer.enabled and self._work_ready():
            with self.tracer.span("serve.step"):
                return self._turn()
        return self._turn()

    def _turn(self) -> int:
        """The dispatch half of :meth:`step`: pick the turn's owner, then
        dispatch its work."""
        order = ("multiply", "stencil", "solve")
        for _ in range(self.cfg.hosts):
            host = self._rr_host
            self._rr_host = (self._rr_host + 1) % self.cfg.hosts
            if self.health.is_quarantined(host):
                continue
            groups = self._pending_groups(host)
            if not groups:
                continue
            group = self._sched.next_group(sorted(groups))
            if group is None:  # pragma: no cover - groups is non-empty
                continue
            pending = groups[group]
            last = self._last_kind.get((host, group), "multiply")
            start = order.index(last) if last in order else 0
            for off in range(1, len(order) + 1):
                kind = order[(start + off) % len(order)]
                if kind not in pending:
                    continue
                self._last_kind[(host, group)] = kind
                if kind == "stencil":
                    return self._step_stencil(host, group)
                if kind == "solve":
                    return self._step_solve(host, group)
                return self._step_batch(host, group)
        return 0

    def _pending_groups(self, host: int) -> dict[GroupKey, set[str]]:
        """Pending work on ``host`` keyed by (tenant, SLO class) group, each
        with its waiting kinds.  The single active solve counts for ITS
        group only and suppresses other groups' queued solves (one solve
        seat per host — their turn comes when it retires)."""
        groups = {
            g: set(kinds)
            for g, kinds in
            self._batchers[host].pending_kinds_by_group().items()
        }
        active = self._solves.get(host)
        if active is not None:
            owner = active["req"].group
            for g, kinds in groups.items():
                if g != owner:
                    kinds.discard("solve")
            groups.setdefault(owner, set()).add("solve")
        return {g: kinds for g, kinds in groups.items() if kinds}

    def _step_batch(self, host: int, group: GroupKey | None = None) -> int:
        """One coalesced fused-k dispatch for ``host``, drawn from
        ``group``'s buckets when the fair scheduler granted the turn to a
        specific (tenant, class) group."""
        batch = self._batchers[host].next_batch(group=group)
        if batch is None:
            return 0
        reqs = batch.requests
        tr = self.tracer
        if tr.enabled:
            self._seat_popped(reqs)
        runner = self.runner_for(batch.L, host)
        n_sites = batch.L**4
        if self.faults.enabled:
            f = self._dispatch_fault(host, "multiply", "batch")
            if f is not None:
                # launch failed: every popped request goes down the retry
                # path (backoff requeue, or structured exhaustion)
                quarantined = self.health.record_failure(host, "dispatch")
                for r in reqs:
                    self._retry_or_fail(r, "injected dispatch failure")
                if quarantined:
                    self._quarantine(host)
                return 0
        with tr.span("serve.stack", lane=host) as span:
            if tr.enabled:
                span.set(live=len(reqs), padded=batch.padded_size)
            a = _host_stack([r.a for r in reqs], batch.pad)
            b = _host_stack([r.b for r in reqs], batch.pad)
        shape_key = self._shape_key(runner, batch.L, batch.k, batch.padded_size)
        cold = shape_key not in self._seen_shapes
        flops = request_flops(n_sites, batch.k) * len(reqs)
        with self._dispatch_span(runner, host, "multiply", batch.L, batch.k,
                                 "batch", live=len(reqs),
                                 padded=batch.padded_size, flops=flops,
                                 cold=cold):
            t0 = time.perf_counter()
            runner.tracer = tr  # the runner records its steps on it
            c = runner.multiply(a, b, k=batch.k)
            if self.faults.enabled:
                c = self._poison_output(c, host, "multiply")
            c.block_until_ready()
            step_s = time.perf_counter() - t0
        if (self.faults.enabled or self.cfg.numerics_guard) \
                and not self._finite(c):
            # poisoned (or genuinely non-finite) output: never delivered —
            # the batch re-runs through the retry path, bitwise clean
            quarantined = self.health.record_failure(host, "non-finite output")
            for r in reqs:
                self._retry_or_fail(r, "non-finite kernel output")
            if quarantined:
                self._quarantine(host)
            return 0
        if self.faults.enabled or self.cfg.numerics_guard:
            self.health.record_success(host)
        self._seen_shapes.add(shape_key)
        self.metrics.record_dispatch(
            live=len(reqs), padded=batch.padded_size, step_s=step_s,
            flops=flops, cold=cold, host=host,
        )
        done_s = time.perf_counter()
        for i, r in enumerate(reqs):
            self._results[r.req_id] = c[i]
            self.metrics.record_completion(
                done_s - r.arrival_s, tenant=r.tenant, slo=r.slo)
            if tr.enabled:
                self._trace_request(r, done_s, host, "batch")
        self.metrics.record_queue_depth(self.queued())
        return len(reqs)

    def _stencil_step_for(self, runner: BatchedLatticeRunner, host: int, L: int):
        """The host's jitted, vmapped stencil dispatch for L — built once per
        warm-pool entry from the plan's reference stencil (the serving path
        runs on a host-local submesh, where the overlap schedule degenerates
        to the reference anyway).  Dispatch parity with the multiply path:
        the batch axis shards whole request lattices over the host's devices
        (the same placement ``BatchedLatticeRunner.run`` gives multiplies).
        """
        ecfg = runner.cfg
        key = (host, L, ecfg.dtype, ecfg.layout.value, ecfg.tile, ecfg.compression)
        step = self._stencil_steps.get(key)
        if step is None:
            plan = runner.plan
            u_spec = plan.lattice_batch_sharding().spec
            v_spec = P(u_spec[0], None, None, None)
            step = jax.jit(
                site_local(
                    jax.vmap(plan.raw_stencil_reference(sharded=False)),
                    plan.mesh, (u_spec, v_spec), v_spec,
                ),
                out_shardings=NamedSharding(plan.mesh, v_spec),
            )
            self._stencil_steps[key] = step
        return step

    def _step_stencil(self, host: int, group: GroupKey | None = None) -> int:
        """One coalesced stencil dispatch for ``host``: the granted group's
        oldest waiting lattice size, vmapped through the warm runner's
        plan."""
        batch = self._batchers[host].next_stencil_batch(group=group)
        if batch is None:
            return 0
        reqs = batch.requests
        tr = self.tracer
        if tr.enabled:
            self._seat_popped(reqs)
        runner = self.runner_for(batch.L, host)
        plan = runner.plan
        n_sites = batch.L**4
        if self.faults.enabled:
            f = self._dispatch_fault(host, "stencil", "batch")
            if f is not None:
                quarantined = self.health.record_failure(host, "dispatch")
                for r in reqs:
                    self._retry_or_fail(r, "injected dispatch failure")
                if quarantined:
                    self._quarantine(host)
                return 0
        # warm-size padding (jit-cache control) + device-multiple padding
        # (whole lattices per device, as the multiply path's run() pads)
        dispatched = batch.padded_size + (-batch.padded_size) % runner.n_devices
        pad = dispatched - len(reqs)
        with tr.span("serve.stack", lane=host) as span:
            if tr.enabled:
                span.set(live=len(reqs), padded=dispatched)
            u = _host_stack([r.a for r in reqs], pad)
            v = _host_stack([r.b for r in reqs], pad)
        u_phys = runner.pack_batch(u)
        v_p = runner.pack_vec_batch(v)
        step = self._stencil_step_for(runner, host, batch.L)
        shape_key = ("stencil", batch.L, dispatched)
        cold = shape_key not in self._seen_shapes
        flops = float(STENCIL_FLOPS_PER_SITE) * n_sites * len(reqs)
        with self._dispatch_span(runner, host, "stencil", batch.L, 1, "batch",
                                 live=len(reqs), padded=dispatched,
                                 flops=flops, cold=cold):
            t0 = time.perf_counter()
            out_p = step(u_phys, v_p)
            if self.faults.enabled:
                out_p = self._poison_output(out_p, host, "stencil")
            out_p.block_until_ready()
            step_s = time.perf_counter() - t0
        if (self.faults.enabled or self.cfg.numerics_guard) \
                and not self._finite(out_p):
            quarantined = self.health.record_failure(host, "non-finite output")
            for r in reqs:
                self._retry_or_fail(r, "non-finite kernel output")
            if quarantined:
                self._quarantine(host)
            return 0
        if self.faults.enabled or self.cfg.numerics_guard:
            self.health.record_success(host)
        self._seen_shapes.add(shape_key)
        self.metrics.record_dispatch(
            live=len(reqs), padded=dispatched, step_s=step_s, flops=flops,
            cold=cold, host=host,
        )
        done_s = time.perf_counter()
        for i, r in enumerate(reqs):
            self._results[r.req_id] = plan.unpack_vec(out_p[i], n_sites)
            self.metrics.record_completion(
                done_s - r.arrival_s, tenant=r.tenant, slo=r.slo)
            if tr.enabled:
                self._trace_request(r, done_s, host, "batch")
        self.metrics.record_queue_depth(self.queued())
        return len(reqs)

    def _seat_solve(self, host: int,
                    group: GroupKey | None = None) -> dict[str, Any] | None:
        """Pop the granted group's oldest queued solve and seat it as the
        active one: pack the gauge field and right-hand side through the
        warm runner's plan, initialize the CG state, and pin the
        convergence threshold ``||r||^2 <= tol^2 ||b||^2`` from the packed
        b."""
        req = self._batchers[host].next_solve(group=group)
        if req is None:
            return None
        runner = self.runner_for(req.L, host)
        if (self._brownout is not None and self._brownout.rung >= 2
                and self.cfg.brownout.degrade_bulk_bf16
                and req.slo == SLO_BULK
                and runner.cfg.dtype != "bfloat16"):
            # rung 2 degradation: a BULK solve rides a warm bf16-storage
            # plan when the pool already holds one for this (host, L) —
            # never builds a new plan mid-overload
            for key, cand in self._pool.items():
                if key[0] == host and key[1] == req.L \
                        and key[2] == "bfloat16":
                    runner = cand
                    break
        plan = runner.plan
        tr = self.tracer
        with (tr.span("serve.seat", lane=host, req_id=req.req_id,
                      operator=_solve_operator(req)) if tr.enabled
              else NULL_TRACER.span("serve.seat")):
            if req.mass is None:
                u_phys, b_p = plan.pack_gauge(req.a), plan.pack_rhs(req.b)
                unpack = plan.unpack_vec
            else:
                u_phys = plan.prepare_staggered(req.a, req.mass)
                b_p, unpack = plan.pack_half(req.b), plan.unpack_half
            state = plan.cg_state_init(b_p)
            if tr.enabled:
                jax.block_until_ready(u_phys)
            b_rs = float(jax.device_get(state["rs"]))  # r_0 = b: rs_0 = ||b||^2
        active = {
            "req": req, "plan": plan, "runner": runner, "u_phys": u_phys,
            "unpack": unpack, "state": state, "b_rs": b_rs,
            "stop2": req.tol * req.tol * b_rs,
            "best": None,  # (rs_host, x) — carried on structured failures
        }
        self._solves[host] = active
        if self.tracer.enabled:
            req.seated_s = time.perf_counter()
            self.tracer.event(
                "seat", lane=_request_lane(req.req_id), req_id=req.req_id,
                L=req.L, host=host, kind="solve")
        return active

    def _step_solve(self, host: int, group: GroupKey | None = None) -> int:
        """Advance the host's active solve by ``solve_iters_per_step`` CG
        iterations (seating the granted group's oldest queued solve first
        if none is active); retires it — its seat and queue budget free
        immediately — once the residual crosses tol or
        ``max_iters`` runs out, delivering the best iterate either way."""
        active = self._solves.get(host)
        if active is None:
            active = self._seat_solve(host, group)
            if active is None:
                return 0
        req, plan, state = active["req"], active["plan"], active["state"]
        if active["b_rs"] == 0.0:
            # zero right-hand side: x = 0 exactly; retire without iterating
            # (CG's alpha = <r,r>/<p,Ap> is 0/0 on this input)
            return self._retire_solve(host, active, state)
        if self.faults.enabled:
            f = self._dispatch_fault(host, "solve", "solve")
            if f is not None:
                # failed launch unseats the solve; a retry re-seats it fresh
                # (CG restarts are deterministic — same b, same schedule)
                del self._solves[host]
                quarantined = self.health.record_failure(host, "dispatch")
                self._retry_or_fail(req, "injected dispatch failure")
                if quarantined:
                    self._quarantine(host)
                return 0
        n = min(self.cfg.solve_iters_per_step,
                req.max_iters - state["iterations"])
        if (self._brownout is not None and self._brownout.rung >= 2
                and req.slo == SLO_BULK):
            # rung 2: bulk solves advance fewer CG iterations per turn,
            # returning turns to the latency lane sooner
            n = max(1, n // self.cfg.brownout.degrade_solve_factor)
            self.metrics.record_degraded_solve_turn()
        runner = active["runner"]
        operator = _solve_operator(req)
        shape_key = ("solve", req.L, operator, req.mass)
        cold = shape_key not in self._seen_shapes
        tr = self.tracer
        # the staggered iteration's two half-lattice passes cost one stencil
        # pass over the lattice: the same nominal flops per site
        flops = float(CG_ITER_FLOPS_PER_SITE) * req.n_sites * n
        with self._dispatch_span(runner, host, "solve", req.L, n, "solve",
                                 live=1, padded=1, flops=flops, cold=cold):
            t0 = time.perf_counter()
            for _ in range(n):
                if tr.enabled:
                    with tr.span("cg.iter", lane=host, req_id=req.req_id,
                                 it=state["iterations"] + 1, operator=operator):
                        state = plan.cg_iterate(active["u_phys"], state)
                        jax.block_until_ready(state["rs"])
                else:
                    state = plan.cg_iterate(active["u_phys"], state)
            if tr.enabled:
                tr.count("dslash_applies", n * (1 if req.mass is None else 2))
            if self.faults.enabled:
                # "kernel" seam for solves: poison the chunk's residual
                # scalar — the corrupted-iterate case the residual guard
                # below must catch
                fk = self.faults.ask("kernel", host=host, kind="solve")
                if fk is not None:
                    self.metrics.record_fault()
                    if tr.enabled:
                        tr.event("chaos.fault", lane=host, site="kernel",
                                 action=fk.action, seq=fk.seq, host=host,
                                 kind="solve")
                    state["rs"] = jnp.full_like(state["rs"], float("nan"))
            if tr.enabled:
                with tr.span("cg.reduce", lane=host, req_id=req.req_id,
                             it=state["iterations"]):
                    rs_host = float(jax.device_get(state["rs"]))
            else:
                rs_host = float(jax.device_get(state["rs"]))  # syncs the chunk
            step_s = time.perf_counter() - t0
        active["state"] = state
        if self.faults.enabled or self.cfg.numerics_guard:
            # CG residual guard: NaN/Inf or blow-up is numerical breakdown —
            # structured failure carrying the best iterate, never a hang
            bad = not math.isfinite(rs_host) or (
                rs_host > CG_DIVERGENCE_FACTOR * active["b_rs"])
            if bad:
                del self._solves[host]
                reason = ("non-finite residual" if not math.isfinite(rs_host)
                          else "diverged")
                quarantined = self.health.record_failure(host, f"cg {reason}")
                best = active["best"]
                residual = (rs_host / active["b_rs"]) ** 0.5 \
                    if math.isfinite(rs_host) else float("nan")
                terminal = CGDivergedError(
                    state["iterations"], residual, req.tol, reason=reason)
                # canonical best iterate rides along for the caller (same
                # shape the request's normal result would have had)
                terminal.partial = (
                    None if best is None else active["unpack"](best[1]))
                self._retry_or_fail(req, f"cg {reason}", terminal=terminal)
                if quarantined:
                    self._quarantine(host)
                return 0
            self.health.record_success(host)
            best = active["best"]
            if best is None or rs_host < best[0]:
                active["best"] = (rs_host, state["x"])
        self._seen_shapes.add(shape_key)
        self.metrics.record_dispatch(
            live=1, padded=1, step_s=step_s, flops=flops, cold=cold, host=host,
        )
        self.metrics.record_iteration("solve", n)
        if rs_host <= active["stop2"] or state["iterations"] >= req.max_iters:
            return self._retire_solve(host, active, state)
        self.metrics.record_queue_depth(self.queued())
        return 0

    def _retire_solve(self, host: int, active: dict[str, Any],
                      state: dict[str, Any]) -> int:
        """Deliver the active solve's iterate and free its seat."""
        req = active["req"]
        self._results[req.req_id] = active["unpack"](state["x"])
        del self._solves[host]
        done_s = time.perf_counter()
        self.metrics.record_completion(
            done_s - req.arrival_s, tenant=req.tenant, slo=req.slo)
        if self.tracer.enabled:
            self._trace_request(req, done_s, host, "solve")
        self.metrics.record_queue_depth(self.queued())
        return 1

    def run_until_drained(self, max_steps: int = 10_000) -> int:
        """Step until queues, retry backoffs AND active solves empty;
        returns completed."""
        total = 0
        for _ in range(max_steps):
            if not self._work_pending():
                return total
            n = self.step()
            total += n
            if n == 0 and self._retry_q and not self._work_ready():
                # only backoff waits remain: sleep to the earliest eligible
                # retry instead of spinning max_steps away
                nxt = min(t for t, _ in self._retry_q)
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.01)))
        raise RuntimeError(f"queue not drained after {max_steps} steps")

    # -- results -------------------------------------------------------------

    def has_result(self, req_id: int) -> bool:
        return req_id in self._results

    def pop_result(self, req_id: int) -> Any:
        """The canonical complex result for a completed request (once) — or
        the structured failure object (:class:`RequestFailure` subclass, or
        a ``CGDivergedError``) the request resolved with; check
        ``isinstance(out, Exception)``.  ``arun`` raises these instead."""
        return self._results.pop(req_id)

    def pop_ready(self) -> dict[int, jax.Array]:
        """All completed results, cleared from the service (delivery drain).

        A caller that steps the service itself (replay harnesses, pollers)
        must drain results this way or via ``pop_result`` — undelivered C
        lattices are device arrays and accumulate for the service lifetime.
        Results owned by a pending :meth:`arun` coroutine are left in place;
        only that coroutine delivers them.
        """
        if not self._awaited:
            out, self._results = self._results, {}
            return out
        out = {rid: c for rid, c in self._results.items() if rid not in self._awaited}
        for rid in out:
            del self._results[rid]
        return out

    # -- asyncio face --------------------------------------------------------

    async def arun(self, a: jax.Array, b: jax.Array, k: int | None = None,
                   deadline_s: float | None = None,
                   tenant: str = DEFAULT_TENANT,
                   slo: str | None = None) -> jax.Array:
        """Submit and await one request from an asyncio front-end.

        Concurrent ``arun`` coroutines submitted in the same scheduler tick
        coalesce into one dispatch — whichever coroutine steps first serves
        the whole bucket.  Backpressure surfaces as cooperative retry with
        CAPPED EXPONENTIAL BACKOFF: the first rejection yields to the loop
        (letting other coroutines drain the queue) and retries immediately;
        sustained rejection sleeps the retry policy's jittered, capped
        schedule instead of pegging the event loop with submit attempts.
        Quota backpressure (a dry tenant bucket) rides the same loop — the
        coroutine backs off until the bucket refills.  A request that
        resolves with a structured failure (deadline, shed, brownout,
        retries exhausted, CG divergence) RAISES it here.
        """
        req_id = self.submit(a, b, k, deadline_s=deadline_s,
                             tenant=tenant, slo=slo)
        attempt = 0
        while req_id is None:
            if attempt == 0:
                await asyncio.sleep(0)  # same-tick coalescing fast path
            else:
                await asyncio.sleep(
                    self.cfg.retry.backoff_s(attempt, self._retry_rng))
            attempt += 1
            self.step()
            req_id = self.submit(a, b, k, deadline_s=deadline_s,
                                 tenant=tenant, slo=slo)
        self._awaited.add(req_id)  # shield from a concurrent pop_ready drain
        try:
            while not self.has_result(req_id):
                await asyncio.sleep(0)
                self.step()
            out = self.pop_result(req_id)
            if isinstance(out, Exception):
                raise out
            return out
        finally:
            self._awaited.discard(req_id)
