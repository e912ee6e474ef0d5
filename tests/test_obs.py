"""repro.obs: tracer invariants, exports, stats, provenance, attribution.

The observability layer's contract is sharp enough to pin exactly:
spans nest via the context stack, the flight recorder is bounded, the
disabled path allocates nothing, both exports round-trip, and the
attribution join reproduces the roofline's terms for any traced config.
"""
import json

import pytest

from repro.obs import (
    NULL_TRACER,
    REQUIRED_PROVENANCE_KEYS,
    Reservoir,
    RunningStat,
    Tracer,
    attribution_report,
    overlap_efficiency_from_spans,
    provenance_block,
    provenance_problems,
)
from repro.obs.tracer import _NULL_SPAN, load_jsonl


# -- span nesting / ordering --------------------------------------------------


def test_span_nesting_and_completion_order():
    tr = Tracer()
    with tr.span("outer", lane=3) as outer:
        with tr.span("inner") as inner:
            pass
        with tr.span("inner2") as inner2:
            pass
    spans = tr.spans()
    # children complete (enter the ring) before the parent
    assert [s.name for s in spans] == ["inner", "inner2", "outer"]
    assert inner.parent_id == outer.span_id
    assert inner2.parent_id == outer.span_id
    assert outer.parent_id is None
    # lane inheritance: nested spans ride the stack top's lane
    assert inner.lane == 3 and inner2.lane == 3
    # monotonic containment
    assert outer.t0_s <= inner.t0_s <= inner.t1_s <= outer.t1_s
    assert inner.t1_s <= inner2.t0_s  # sequential siblings ordered


def test_retroactive_and_event_spans_attach_to_stack():
    tr = Tracer()
    with tr.span("step") as step:
        tr.add_span("timed", 1.0, 2.0, lane=7, note="retro")
        tr.event("marker", x=1)
    retro = next(s for s in tr.spans() if s.name == "timed")
    marker = next(s for s in tr.spans() if s.name == "marker")
    assert retro.parent_id == step.span_id and retro.dur_s == 1.0
    assert marker.parent_id == step.span_id and marker.dur_s == 0.0
    # explicit parent wins over the stack
    tr.add_span("orphan", 0.0, 1.0, parent_id=None)
    assert tr.spans()[-1].name == "orphan"


def test_out_of_order_exit_does_not_corrupt_stack():
    tr = Tracer()
    a = tr.span("a")
    b = tr.span("b")
    a_span = a.__enter__()
    b.__enter__()
    a.__exit__(None, None, None)  # exits before its child
    b.__exit__(None, None, None)
    with tr.span("after") as after:
        pass
    assert after.parent_id is None  # stack drained despite the misnesting
    assert a_span.span_id is not None


# -- flight-recorder ring -----------------------------------------------------


def test_ring_buffer_evicts_oldest_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.add_span(f"s{i}", float(i), float(i) + 0.5)
    assert len(tr) == 4
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


# -- disabled fast path -------------------------------------------------------


def test_disabled_tracer_allocates_nothing():
    assert NULL_TRACER.enabled is False
    # one shared module-level no-op span serves every call
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    assert NULL_TRACER.span("a") is _NULL_SPAN
    with NULL_TRACER.span("a") as s:
        assert s.set(x=1) is s
    assert NULL_TRACER.add_span("x", 0.0, 1.0) is None
    assert NULL_TRACER.event("x") is None
    NULL_TRACER.count("n")
    assert NULL_TRACER.counters == {}
    assert len(NULL_TRACER) == 0
    assert NULL_TRACER.absorb([{"name": "s", "ts_s": 0, "dur_s": 1}]) == 0


# -- exports ------------------------------------------------------------------


def _sample_tracer() -> Tracer:
    tr = Tracer()
    with tr.span("stencil.step", lane=2, L=4, overlap=True):
        with tr.span("stencil.exchange"):
            pass
        with tr.span("stencil.interior"):
            pass
    tr.count("dispatches", 3)
    return tr


def test_jsonl_roundtrip(tmp_path):
    tr = _sample_tracer()
    p = tmp_path / "t.jsonl"
    n = tr.to_jsonl(str(p))
    records = load_jsonl(str(p))
    assert n == len(records) == 4  # 3 spans + 1 counter
    spans = [r for r in records if r["type"] == "span"]
    byname = {r["name"]: r for r in spans}
    assert byname["stencil.exchange"]["parent_id"] == \
        byname["stencil.step"]["span_id"]
    assert records[-1] == {"type": "counter", "name": "dispatches", "value": 3}


def test_chrome_trace_event_validity(tmp_path):
    tr = _sample_tracer()
    payload = tr.chrome_trace(metadata={"git_sha": "abc"})
    assert set(payload) == {"traceEvents", "displayTimeUnit", "otherData"}
    for ev in payload["traceEvents"]:
        # complete events: the exact keys chrome://tracing/Perfetto require
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert ev["dur"] >= 0.0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert ev["cat"] == "stencil"
    assert payload["otherData"]["git_sha"] == "abc"
    assert payload["otherData"]["counters"] == {"dispatches": 3}
    p = tmp_path / "t.chrome.json"
    assert tr.to_chrome_trace(str(p)) == 3
    json.load(open(p))  # must be ONE valid JSON document


def test_absorb_preserves_forward_parent_links():
    """Ring order is completion order — children precede parents — so the
    id remap must resolve forward references."""
    sub = _sample_tracer()
    records = [s.as_dict() for s in sub.spans()]
    parent = Tracer()
    with parent.span("local"):
        pass
    assert parent.absorb(records, lane_offset=100) == 3
    byname = {s.name: s for s in parent.spans()}
    step, exch = byname["stencil.step"], byname["stencil.exchange"]
    assert exch.parent_id == step.span_id
    assert step.span_id != records[-1]["span_id"] or True  # remapped ids
    assert step.lane == 102  # lane offset applied
    ids = [s.span_id for s in parent.spans()]
    assert len(ids) == len(set(ids))  # no collisions with local spans


# -- bounded stats ------------------------------------------------------------


def test_reservoir_exact_below_capacity_bounded_above():
    r = Reservoir(capacity=100, seed=0)
    r.extend(float(i) for i in range(50))
    assert len(r) == 50 and sorted(r.sample) == [float(i) for i in range(50)]
    assert r.percentile(50) == pytest.approx(24.5)
    r.extend(float(i) for i in range(50, 100_000))
    assert len(r) == 100_000          # count stays exact
    assert len(r.sample) == 100       # memory stays bounded
    assert r.mean() == pytest.approx(49999.5)  # mean from exact running total
    # the subsample still estimates the distribution (uniform 0..1e5)
    assert r.percentile(50) == pytest.approx(50_000, rel=0.25)


def test_running_stat():
    s = RunningStat()
    assert s.mean() == 0.0 and s.max_or(42) == 42
    for v in (1.0, 3.0, 2.0):
        s.add(v)
    assert s.mean() == pytest.approx(2.0) and s.max_or(0) == 3.0


def test_service_metrics_memory_is_bounded():
    from repro.serve.su3.metrics import LATENCY_RESERVOIR_CAPACITY, ServiceMetrics
    m = ServiceMetrics()
    for i in range(3 * LATENCY_RESERVOIR_CAPACITY):
        m.record_completion(0.010)
        m.record_queue_depth(i % 7)
    assert len(m.latencies_s.sample) == LATENCY_RESERVOIR_CAPACITY
    snap = m.snapshot()
    assert snap["completed"] == 3 * LATENCY_RESERVOIR_CAPACITY
    assert snap["latency_p50_ms"] == pytest.approx(10.0)
    assert snap["queue_depth_max"] == 6


# -- provenance ---------------------------------------------------------------


def test_provenance_block_is_complete():
    block = provenance_block()
    for key in REQUIRED_PROVENANCE_KEYS:
        assert key in block, key
        if key != "xla_flags":  # legitimately empty when the env var is unset
            assert block[key] not in (None, ""), key
    assert block["jax_version"] != "unknown"
    assert len(block["git_sha"]) in (40, len("unknown")) or block["git_sha"]


def test_provenance_problems_names_missing_and_drifted_keys():
    good = {"provenance": provenance_block(), "tables": {}}
    assert provenance_problems(good) == []
    assert provenance_problems({"tables": {}})  # no block at all
    broken = {"provenance": dict(good["provenance"]), "tables": {}}
    del broken["provenance"]["device_kind"]
    assert any("device_kind" in p for p in provenance_problems(broken))
    drifted = {"provenance": dict(good["provenance"], backend="tpu")}
    probs = provenance_problems(drifted, good)
    assert len(probs) == 1 and "backend" in probs[0]
    assert provenance_problems(drifted, good, rebaseline_note="ok") == []
    stamped = {"provenance": dict(drifted["provenance"], rebaseline="tpu day")}
    assert provenance_problems(stamped, good) == []


# -- attribution --------------------------------------------------------------


def _mk_records():
    """Synthetic spans for one multiply config + one overlapped schedule."""
    tr = Tracer()
    for _ in range(3):
        tr.add_span("dispatch", 0.0, 0.010, kind="multiply", L=4, tile=64,
                    k=2, dtype="float32", compression="none", live=4,
                    flops=864.0 * 256 * 2 * 4)
    for _ in range(2):
        with tr.span("stencil.step", L=4, tile=64, overlap=True, depth=1,
                     hosts=2, dtype="float32", compression="none",
                     flops=576.0 * 256):
            with tr.span("stencil.exchange"):
                pass
            with tr.span("stencil.interior"):
                pass
            with tr.span("stencil.boundary"):
                pass
    return tr.spans()


def test_attribution_joins_measured_against_roofline():
    rows = attribution_report(_mk_records(), "cpu")
    by_wl = {r["workload"]: r for r in rows}
    mult = by_wl["multiply"]
    assert mult["n_spans"] == 3 and mult["fused_k"] == 2
    # measured: 3 dispatches x 10ms over 3*4 live requests x k=2 multiplies
    assert mult["measured_unit_s"] == pytest.approx(0.030 / 24)
    assert mult["predicted_s"] is not None and mult["delta_frac"] is not None
    assert mult["model_dominant"] in ("compute", "memory", "issue")
    sched = by_wl["stencil_schedule"]
    assert sched["hosts"] == 2 and sched["overlap"] is True
    assert set(sched["phase_s"]) == {"exchange", "interior", "boundary"}
    assert sched["measured_dominant_phase"] in sched["phase_s"]
    assert sched["model_terms"] is not None and "halo_s" in sched["model_terms"]


def test_attribution_accepts_jsonl_records_and_renders(tmp_path):
    from repro.obs import render_attribution
    tr = Tracer()
    for s in _mk_records():
        tr._record(s)
    p = tmp_path / "t.jsonl"
    tr.to_jsonl(str(p), metadata={"device_kind": "cpu"})
    rows = attribution_report(load_jsonl(str(p)))
    assert {r["workload"] for r in rows} == {"multiply", "stencil_schedule"}
    # the kind comes from the trace's meta record, not the reader's device
    assert all(r["predicted_s"] is not None for r in rows)
    text = render_attribution(rows)
    assert "multiply" in text and "L4/t64" in text and "ovl" in text
    assert render_attribution([]).startswith("(no attributable")


def test_attribution_prices_only_a_recorded_device_kind():
    # no recorded kind: measured columns only, never the reader's peaks
    rows = attribution_report(_mk_records())
    assert rows and all(r["predicted_s"] is None for r in rows)
    assert all(r["measured_unit_s"] > 0 for r in rows)
    with pytest.raises(ValueError, match="no peaks recorded"):
        attribution_report(_mk_records(), "TPU v9")


def test_overlap_efficiency_accounting():
    acct = overlap_efficiency_from_spans(_mk_records())
    assert acct["n_steps"] == 2
    assert set(acct["phase_s"]) == {"exchange", "interior", "boundary"}
    assert acct["sum_phases_s"] <= acct["traced_wall_s"]
    assert overlap_efficiency_from_spans([]) is None


# -- traced service (fast: tiny lattice, no autotune) -------------------------


def test_service_emits_request_lifecycle_spans():
    import numpy as np
    import jax.numpy as jnp
    from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service

    tracer = Tracer()
    svc = SU3Service(ServiceConfig(
        autotune=False, tile=16,
        batcher=BatcherConfig(max_batch=2, warm_batch_sizes=(2,)),
    ), tracer=tracer)
    rng = np.random.default_rng(0)
    a = rng.standard_normal((16, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((4, 3, 3, 2)).astype(np.float32)
    ids = [svc.submit(jnp.asarray(a[..., 0] + 1j * a[..., 1], jnp.complex64),
                      jnp.asarray(b[..., 0] + 1j * b[..., 1], jnp.complex64),
                      k=1) for _ in range(2)]
    svc.run_until_drained()
    for rid in ids:
        svc.pop_result(rid)
    names = [s.name for s in tracer.spans()]
    assert names.count("admit") == 2
    assert "dispatch" in names
    assert names.count("request") == 2
    disp = next(s for s in tracer.spans() if s.name == "dispatch")
    assert disp.attrs["kind"] == "multiply" and disp.attrs["live"] == 2
    req = next(s for s in tracer.spans() if s.name == "request")
    assert req.attrs["queue_wait_s"] >= 0.0
    # request spans cover admission -> completion, so they outlast dispatch
    assert req.dur_s >= disp.dur_s


def test_trace_report_reads_the_recorded_device_kind(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "trace_report.py"))
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)
    tr = Tracer()
    for s in _mk_records():
        tr._record(s)
    for suffix in (".jsonl", ".chrome.json"):
        p = str(tmp_path / f"t{suffix}")
        if suffix == ".jsonl":
            tr.to_jsonl(p, metadata={"device_kind": "cpu"})
        else:
            tr.to_chrome_trace(p, metadata={"device_kind": "cpu"})
        records, meta = trace_report.load_records(p)
        assert meta["device_kind"] == "cpu"
        assert all(r.get("type") != "meta" for r in records)
        assert "roofline of cpu" in trace_report.report(p)


# -- the profiler's clock -----------------------------------------------------


def _profiled_su3_events(log_dir):
    """``(plane, name)`` of every ``su3.*`` event in the one profile under
    ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(plane.name, e.name)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("su3.")]


def test_live_span_lands_on_the_profilers_host_plane(tmp_path):
    import jax

    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        tr.add_span("retro", 0.0, 1.0)  # retroactive: the recorder's alone
    events = _profiled_su3_events(str(tmp_path))
    assert ("/host:CPU", "su3.outer") in events
    assert ("/host:CPU", "su3.inner") in events
    assert not any(name == "su3.retro" for _plane, name in events)
    assert [s.name for s in tr.spans()] == ["inner", "outer", "retro"]


def test_no_annotation_with_the_recorder_off_or_no_session(tmp_path):
    import jax

    tr = Tracer()
    ctx = tr.span("unprofiled")
    with ctx:
        assert ctx._annotation is None  # no profiler session records
    with jax.profiler.trace(str(tmp_path)):
        with Tracer(enabled=False).span("off"):
            pass
        with NULL_TRACER.span("null"):
            pass
    assert _profiled_su3_events(str(tmp_path)) == []
    assert [s.name for s in tr.spans()] == ["unprofiled"]


# -- a served batch, span by span (tiny lattice, no autotune) ----------------

_STEPS = ("transfer.h2d", "codec.pack", "device.step", "codec.unpack",
          "transfer.d2h")


def _served_batch(svc, n=2, k=2):
    """Submit ``n`` L=2 multiplies of chain depth ``k`` and drain them."""
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    a = rng.standard_normal((n, 16, 4, 3, 3, 2)).astype(np.float32)
    b = rng.standard_normal((n, 4, 3, 3, 2)).astype(np.float32)
    ids = [svc.submit(jnp.asarray(a[i, ..., 0] + 1j * a[i, ..., 1], jnp.complex64),
                      jnp.asarray(b[i, ..., 0] + 1j * b[i, ..., 1], jnp.complex64),
                      k=k) for i in range(n)]
    svc.run_until_drained()
    return [svc.pop_result(rid) for rid in ids]


def _small_service(tracer=None):
    from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service

    return SU3Service(ServiceConfig(
        autotune=False, tile=16,
        batcher=BatcherConfig(max_batch=2, warm_batch_sizes=(2,)),
    ), tracer=tracer)


def test_batch_dispatch_records_each_step_under_its_dispatch_span():
    tracer = Tracer()
    svc = _small_service(tracer)
    _served_batch(svc, k=2)
    spans = tracer.spans()
    (disp,) = [s for s in spans if s.name == "dispatch"]
    (stack,) = [s for s in spans if s.name == "serve.stack"]
    steps = [s for s in spans if s.name in _STEPS]
    assert [s.name for s in steps] == list(_STEPS)  # one each, in order
    assert all(s.parent_id == disp.span_id for s in steps)
    (turn,) = [s for s in spans if s.name == "serve.step" and s.span_id == disp.parent_id]
    assert stack.attrs == {"live": 2, "padded": 2} and stack.parent_id == turn.span_id
    assert stack.t1_s <= disp.t0_s
    assert disp.attrs["mode"] == "batch" and disp.attrs["k"] == 2
    assert next(s for s in steps if s.name == "device.step").attrs == {"k": 2}
    for s, nxt in zip(steps, steps[1:]):
        assert disp.t0_s <= s.t0_s <= s.t1_s <= nxt.t0_s <= disp.t1_s


def test_transfer_spans_count_the_bytes_moved():
    import numpy as np

    tracer = Tracer()
    svc = _small_service(tracer)
    _served_batch(svc, k=1)
    runner = svc.runner_for(2)
    a_phys = runner.pack_batch(np.zeros((2, 16, 4, 3, 3), np.complex64))
    b_p = runner.plan.pack_links(np.zeros((2, 4, 3, 3), np.complex64))
    h2d = next(s for s in tracer.spans() if s.name == "transfer.h2d")
    d2h = next(s for s in tracer.spans() if s.name == "transfer.d2h")
    assert h2d.attrs["bytes"] == a_phys.nbytes + b_p.nbytes
    assert d2h.attrs["bytes"] == a_phys.nbytes  # C has A's physical form
    assert a_phys.nbytes == 2 * 16 * 72 * 4 and b_p.nbytes == 2 * 72 * 4


def test_queue_wait_ends_where_the_batch_is_popped():
    tracer = Tracer()
    svc = _small_service(tracer)
    _served_batch(svc)
    stack = next(s for s in tracer.spans() if s.name == "serve.stack")
    reqs = [s for s in tracer.spans() if s.name == "request"]
    assert len(reqs) == 2
    for req in reqs:
        wait = req.attrs["queue_wait_s"]
        assert 0.0 <= wait <= stack.t0_s - req.t0_s  # the stacking is not in it


@pytest.mark.parametrize("warm_first", [False, True])
def test_a_tracer_assigned_after_construction_records_the_steps(warm_first):
    svc = _small_service()
    if warm_first:  # the runner exists before the tracer is replaced
        _served_batch(svc)
    svc.tracer = tracer = Tracer()
    _served_batch(svc)
    names = [s.name for s in tracer.spans()]
    for name in _STEPS + ("dispatch", "serve.stack"):
        assert names.count(name) == 1, name
    svc.tracer = NULL_TRACER
    _served_batch(svc)
    assert [s.name for s in tracer.spans()] == names  # the old one hears no more


def test_turns_with_nothing_to_dispatch_record_no_span():
    tracer = Tracer()
    svc = _small_service(tracer)
    for _ in range(3):
        assert svc.step() == 0
    assert tracer.spans() == []
    _served_batch(svc)
    assert [s.name for s in tracer.spans()].count("serve.step") == 1


# -- the staggered operator's spans and counters ------------------------------


def _hot_field(L=2, seed=7):
    import numpy as np

    rng = np.random.default_rng(seed)
    z = rng.normal(size=(L**4 * 4, 3, 3)) + 1j * rng.normal(size=(L**4 * 4, 3, 3))
    q, _r = np.linalg.qr(z)
    u = q.reshape(L**4, 4, 3, 3).astype(np.complex64)
    b = (rng.normal(size=(L**4 // 2, 3)) + 1j * rng.normal(size=(L**4 // 2, 3)))
    return u, b.astype(np.complex64)


def test_staggered_solve_records_prepare_iterations_and_applies():
    from repro.core.su3.plan import EngineConfig, build_plan

    plan = build_plan(EngineConfig(L=2, tile=16))
    plan.tracer = tracer = Tracer()
    u, b = _hot_field()
    field = plan.prepare_staggered(u, 0.635)
    res = plan.cg_solve(field, plan.pack_half(b), tol=1e-6, max_iters=100)
    (prep,) = [s for s in tracer.spans() if s.name == "ks.prepare"]
    assert prep.attrs == {"L": 2, "mass": 0.635, "bytes": field.nbytes}
    assert field.nbytes == 4 * 2 * 36 * 16 * 4  # links only: no tables
    iters = [s for s in tracer.spans() if s.name == "cg.iter"]
    assert iters and all(s.attrs["operator"] == "ks_eo" for s in iters)
    assert all(s.attrs["neighbours"] == "roll" for s in iters)
    # every dispatched iteration, the lagged one included, two passes each
    assert len(iters) == res.iterations + 1
    assert tracer.counters["dslash_applies"] == res.dslash_applies == 2 * len(iters)


def test_site_local_solve_counts_one_apply_an_iteration():
    import numpy as np
    from repro.core.su3.plan import EngineConfig, build_plan

    plan = build_plan(EngineConfig(L=2, tile=16))
    plan.tracer = tracer = Tracer()
    u = np.broadcast_to(np.eye(3, dtype=np.complex64), (16, 4, 3, 3))
    b = np.ones((16, 3), np.complex64)
    res = plan.cg_solve(plan.pack_gauge(u), plan.pack_rhs(b), tol=1e-6)
    iters = [s for s in tracer.spans() if s.name == "cg.iter"]
    assert all(s.attrs["operator"] == "site_local" for s in iters)
    assert all(s.attrs["neighbours"] == "roll" for s in iters)
    assert tracer.counters["dslash_applies"] == res.dslash_applies == len(iters)


@pytest.mark.parametrize("mass", [None, 0.635])
def test_served_solve_is_seated_under_a_live_span(mass):
    import numpy as np

    tracer = Tracer()
    svc = _small_service(tracer)
    u, b = _hot_field()
    if mass is None:  # the site-local operator, on a field it is Hermitian on
        u = np.broadcast_to(np.eye(3, dtype=np.complex64), (16, 4, 3, 3))
        b = np.ones((16, 3), np.complex64)
    rid = svc.submit_solve(u, b, tol=1e-6, max_iters=100, mass=mass)
    svc.run_until_drained()
    assert np.asarray(svc.pop_result(rid)).shape == b.shape
    spans = tracer.spans()
    operator = "site_local" if mass is None else "ks_eo"
    (seat,) = [s for s in spans if s.name == "serve.seat"]
    assert seat.attrs == {"req_id": rid, "operator": operator}
    first = min(s.t0_s for s in spans if s.name == "cg.iter")
    assert seat.t1_s <= first
    assert all(s.attrs["operator"] == operator for s in spans if s.name == "cg.iter")
    iters = sum(s.name == "cg.iter" for s in spans)
    assert tracer.counters["dslash_applies"] == iters * (1 if mass is None else 2)
