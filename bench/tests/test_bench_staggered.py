"""The staggered CG cell and the served CG cell: each driver end to end at
L=4 on the CPU (Pallas in interpret mode) through ``harness.run_cell``, sound
runs correct and broken ones not; the bfloat16 control failing the check;
the plain reference against the full-lattice definition; the iteration's
count pinned; and every new per-layer reader on hand-made records, silent
where what it reads is absent (as on a program that records no such span or
counter)."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts_staggered, data, harness, peaks
from bench.references import staggered_eo_cg

BENCHMARK = harness.load_benchmark()
SMALL_L = 4
SECONDS = {"ks_eo_l32_f32.resident": 1.0, "cg_l32_f32.served": 1.0}


def small_cell(name, seed):
    cell = harness.load_cell(name, seed)
    cell.config = dict(cell.config, L=SMALL_L, sites=SMALL_L**4)
    return cell


_BASE = {}


def base_state(name):
    """One program per cell for the whole module; each test makes new data."""
    if name not in _BASE:
        _BASE[name] = small_cell(name, 1).driver.setup(small_cell(name, 1))
    return _BASE[name]


def run(name, seed=7, trace=False):
    return harness.run_cell(small_cell(name, seed), SECONDS[name], trace,
                            devices=jax.devices(), peaks=None, benchmark=BENCHMARK,
                            started_s=time.perf_counter(), previous=base_state(name))


@pytest.mark.parametrize("name", list(SECONDS))
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e, _ = harness.metrics_for(BENCHMARK, name)
    assert set(out["metrics"]) == {m["name"] for m in e2e}


def test_traced_staggered_run_reads_the_program_counters():
    out = run("ks_eo_l32_f32.resident", seed=2**31 + 77, trace=True)
    assert out["correct"], out["checks"]
    # hot fields at am = 0.635 converge in about 30 iterations at any volume
    assert 28 <= out["metrics"]["ks_iterations"]["value"] <= 34
    # no device plane on the CPU: the trace readers stay silent
    assert not any(m in out["metrics"] for m in
                   ("ks_iter_roofline", "dslash_ms.ks", "idle_share.ks"))


def _zero_half(x):
    return x.at[..., : x.shape[-1] // 2].set(0)


def _staggered_fault(st, fault, mp):
    from repro.core.su3.plan import CGResult

    solve = st.plan.cg_solve

    def broken(field, b, **kw):
        res = solve(field, b, **kw)
        x = {"unchanged": jnp.zeros_like, "half_left_out": _zero_half,
             "answer_altered": lambda x: x.at[0, 0, 0].add(0.05)}[fault](res.x_p)
        return CGResult(x_p=x, iterations=res.iterations, residuals=res.residuals,
                        converged=True, wall_s=res.wall_s,
                        dslash_applies=res.dslash_applies)

    mp.setattr(st.plan, "cg_solve", broken)


def _served_fault(st, fault, mp):
    pop = st.svc.pop_result

    def broken(rid):
        x = np.asarray(pop(rid))
        if fault == "unchanged":
            return np.zeros_like(x)
        if fault == "half_left_out":
            return np.concatenate([np.zeros_like(x[: len(x) // 2]), x[len(x) // 2:]])
        return x + np.where(np.arange(x.size).reshape(x.shape) == 0, 0.05, 0)

    mp.setattr(st.svc, "pop_result", broken)


FAULTS = {"ks_eo_l32_f32.resident": _staggered_fault, "cg_l32_f32.served": _served_fault}


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "answer_altered"])
@pytest.mark.parametrize("name", list(SECONDS))
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[name](base_state(name), fault, monkeypatch)
    out = run(name, seed=11)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", list(SECONDS))
def test_control_fails_the_check(name):
    cell = small_cell(name, 13)
    driver = cell.driver
    st = driver.setup(cell, base_state(name))
    win = driver.window(st, SECONDS[name], False)
    driver.release(st, win)
    sound, control = driver.checks(st, win), driver.control(st, win)
    assert any(control[k] > cell.limits[k] for k in control), control
    assert all(sound[k] <= cell.limits[k] for k in sound), sound


def _full_lattice_solve(u, b_e, L, mass, tol):
    """The same solve from the definition on the whole lattice: vectors that
    vanish on odd sites, D by ``np.roll`` with the phases and boundary on
    each hop, the neighbour's link for the backward hop."""
    t, z, y, x = np.meshgrid(*(np.arange(L),) * 4, indexing="ij")
    even = ((x + y + z + t) % 2 == 0).reshape(-1)
    eta = [np.ones_like(x), (-1) ** x, (-1) ** (x + y), (-1) ** (x + y + z)]
    links = u.astype(np.complex128).reshape(L, L, L, L, 4, 3, 3)

    def dslash(v):  # v (t, z, y, x, 3)
        out = np.zeros_like(v)
        for mu in range(4):
            axis = 3 - mu
            f, b = np.roll(v, -1, axis), np.roll(v, 1, axis)
            back = np.roll(links[..., mu, :, :], 1, axis)
            if mu == 3:
                f[L - 1] *= -1
                b[0] *= -1
            out += eta[mu][..., None] * (
                np.einsum("...kl,...l->...k", links[..., mu, :, :], f)
                - np.einsum("...lk,...l->...k", back.conj(), b))
        return out

    full = np.zeros((L**4, 3), np.complex128)
    full[even] = b_e
    bl = full.reshape(L, L, L, L, 3)
    xs, r, p = np.zeros_like(bl), bl.copy(), bl.copy()
    rs = b_rs = np.vdot(bl, bl).real
    while (rs / b_rs) ** 0.5 > tol:
        ap = 4 * mass**2 * p - dslash(dslash(p))
        alpha = rs / np.vdot(p, ap).real
        xs, r = xs + alpha * p, r - alpha * ap
        rs, rs_old = np.vdot(r, r).real, rs
        p = r + rs / rs_old * p
    return xs.reshape(-1, 3)[even]


@pytest.mark.parametrize("L", [4, 6])
def test_reference_solves_the_full_lattice_definition(L):
    u = data.gauge_field(data.rng(3, 0), L)
    b = data.vector_field(data.rng(3, 1), L)[: L**4 // 2]
    x, it, rel = staggered_eo_cg.solve(u, b, L, 0.635, tol=1e-10)
    assert rel <= 1e-10 and 25 <= it <= 60
    assert staggered_eo_cg.rel_err(x, _full_lattice_solve(u, b, L, 0.635, 1e-10)) < 1e-8


@pytest.mark.parametrize("value, expected", [
    (counts_staggered.KS_WORDS_PER_EVEN_SITE, 366),
    (counts_staggered.KS_FLOPS_PER_EVEN_SITE, 1224),
    (counts_staggered.iteration(32).bytes, 366 * 4 * 32**4 // 2),
    (counts_staggered.iteration(32).flops, 1224 * 32**4 // 2),
])
def test_iteration_counts(value, expected):
    assert value == expected


def test_iteration_floor_is_bandwidth_bound_on_v5e():
    work = counts_staggered.iteration(32)
    assert work.floor_s(peaks.TPU_V5E, "float32") == pytest.approx(work.bytes / 819e9)


def _record(cell, counters, trace):
    return harness.Record(cell=harness.load_cell(cell, 1), trace=trace,
                          counters=counters, peaks=peaks.TPU_V5E)


def _read(name, cell, counters, trace):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(
        _record(cell, counters, trace))


KS = "ks_eo_l32_f32.resident"
SERVED = "cg_l32_f32.served"
# two solves of 31 and 33 iterations, each dispatching one more (the lagged
# check), two half-lattice passes an iteration; the chip busy 6.4 s
KS_COUNTERS = {"iterations": [31, 33], "dslash_applies": [64, 68]}
TRACE = SimpleNamespace(busy_s=6.4, window_s=6.5, glue_share=lambda: 93.0,
                        idle_share=lambda: 1.5)
SEAT_SPANS = [{"name": "serve.seat", "dur_s": s, "attrs": {}} for s in (1.2, 1.6)]


@pytest.mark.parametrize("name, cell, counters, expected", [
    ("ks_iterations", KS, KS_COUNTERS, 32.0),
    ("dslash_ms.ks", KS, KS_COUNTERS, 1e3 * 6.4 / 132),
    ("ks_iter_roofline", KS, KS_COUNTERS,
     100 * counts_staggered.iteration(32).bytes / 819e9 / (6.4 / 64)),
    ("glue_share.ks", KS, KS_COUNTERS, 93.0),
    ("idle_share.ks", KS, KS_COUNTERS, 1.5),
    ("seat_ms.cg_served", SERVED, {"spans": SEAT_SPANS}, 1400.0),
    ("idle_share.cg_served", SERVED, {"spans": SEAT_SPANS}, 1.5),
])
def test_reader_on_hand_made_records(name, cell, counters, expected):
    assert _read(name, cell, counters, TRACE) == pytest.approx(expected)


@pytest.mark.parametrize("name, cell, counters", [
    ("ks_iterations", KS, {}),
    ("dslash_ms.ks", KS, {"iterations": [31]}),  # a program with no such counter
    ("ks_iter_roofline", KS, {}),
    ("seat_ms.cg_served", SERVED, {"spans": []}),  # a program with no such span
])
def test_reader_is_silent_without_what_it_reads(name, cell, counters):
    assert _read(name, cell, counters, TRACE) is None


@pytest.mark.parametrize("name", ["ks_iter_roofline", "dslash_ms.ks", "glue_share.ks",
                                  "idle_share.ks", "idle_share.cg_served"])
def test_trace_readers_are_silent_without_a_trace(name):
    cell = SERVED if name.endswith("cg_served") else KS
    assert _read(name, cell, KS_COUNTERS, None) is None


def test_new_metrics_name_only_their_cells():
    entries = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in ("ks_iter_roofline", "glue_share.ks", "idle_share.ks",
                 "ks_iterations", "dslash_ms.ks"):
        assert entries[name]["workloads"] == [KS]
        assert entries[name]["moves"] == "solve_s"
    for name in ("seat_ms.cg_served", "idle_share.cg_served"):
        assert entries[name]["workloads"] == [SERVED]
        assert entries[name]["moves"] == "request_p50_s"
