"""Each traffic driver's set-up, window and check, end to end at L=4 on the
CPU (Pallas in interpret mode), through ``harness.run_cell`` with the chip
lookup skipped: sound runs come out correct; runs with the timed path broken
underneath, and the lower-precision control, come out not correct."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness

BENCHMARK = harness.load_benchmark()
SMALL_L = 4
SECONDS = {"su3bench_l32_f32.loop": 0.5, "cg_l32_f32.resident": 1.0,
           "su3bench_l32_f32.served": 1.5}


def small_cell(name, seed):
    cell = harness.load_cell(name, seed)
    cell.config = dict(cell.config, L=SMALL_L, sites=SMALL_L**4)
    if "k" in cell.params:  # fewer chain depths to compile in interpret mode
        cell.workload = dict(cell.workload, params=dict(cell.params, k=[1, 2]))
    return cell


_BASE = {}


def base_state(name):
    """One program per cell for the whole module; each test makes new data."""
    if name not in _BASE:
        _BASE[name] = small_cell(name, 1).driver.setup(small_cell(name, 1))
    return _BASE[name]


def run(name, seed=7, trace=False):
    cell = small_cell(name, seed)
    return harness.run_cell(cell, SECONDS[name], trace, devices=jax.devices(),
                            peaks=None, benchmark=BENCHMARK,
                            started_s=time.perf_counter(),
                            previous=base_state(name))


@pytest.mark.parametrize("name", list(SECONDS))
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e, _ = harness.metrics_for(BENCHMARK, name)
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    assert list(out)[-1] == "checks"


def test_traced_run_without_a_chip_reads_no_device_metric():
    out = run("su3bench_l32_f32.loop", trace=True)
    assert out["correct"]
    assert not any(m.endswith("roofline") or m.startswith("idle_share")
                   for m in out["metrics"])
    assert out["device"]["busy_s"] == 0.0 and "breakdown" in out


def test_served_windows_serve_whole_blocks_of_depths():
    """However short the window, each caller completes whole blocks of the
    depth sequence, so every window serves the same mix of k."""
    name = "su3bench_l32_f32.served"
    cell = small_cell(name, 17)
    st = cell.driver.setup(cell, base_state(name))
    block = len(cell.params["k"]) * cell.params["callers"]
    for seconds in (0.01, 0.3):
        win = cell.driver.window(st, seconds, False)
        assert win.failed == 0
        assert win.counters["completed"] % block == 0 and win.counters["completed"] > 0


def _zero_half(x, axis):
    """``x`` with the first half of the live sites along ``axis`` zeroed."""
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(0, SMALL_L**4 // 2)
    return x.at[tuple(idx)].set(0)


def _loop_fault(st, fault, mp):
    step = st.plan.step
    broken = {
        "unchanged": lambda a, b: a,
        "half_left_out": lambda a, b: _zero_half(step(a, b), -1),
        "answer_altered": lambda a, b: step(a, b).at[0, 0, 0].add(1e-3),
    }[fault]
    mp.setattr(st.plan, "step", broken)


def _cg_fault(st, fault, mp):
    from repro.core.su3.plan import CGResult

    solve = st.plan.cg_solve

    def broken(u, b, **kw):
        res = solve(u, b, **kw)
        x = {"unchanged": lambda x: jnp.zeros_like(x),
             "half_left_out": lambda x: _zero_half(x, -1),
             "answer_altered": lambda x: x.at[0, 0, 0].add(0.05)}[fault](res.x_p)
        return CGResult(x_p=x, iterations=res.iterations, residuals=res.residuals,
                        converged=True, wall_s=res.wall_s)

    mp.setattr(st.plan, "cg_solve", broken)


def _served_fault(st, fault, mp):
    runner = st.svc.runner_for(SMALL_L)
    multiply = runner.multiply
    cpu = jax.devices("cpu")[0]

    def broken(a, b, k=1):
        if fault == "unchanged":
            return jax.device_put(np.asarray(a), cpu)
        c = multiply(a, b, k=k)
        if fault == "half_left_out":
            return _zero_half(c, 1)
        return c.at[0, 0, 0, 0, 0].add(1e-3)

    mp.setattr(runner, "multiply", broken)


FAULTS = {"su3bench_l32_f32.loop": _loop_fault, "cg_l32_f32.resident": _cg_fault,
          "su3bench_l32_f32.served": _served_fault}


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "answer_altered"])
@pytest.mark.parametrize("name", list(SECONDS))
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[name](base_state(name), fault, monkeypatch)
    out = run(name, seed=11)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", list(SECONDS))
def test_control_fails_the_check(name):
    """The reference in bfloat16 storage, put in the program's place, reads
    above every limit it is held to."""
    cell = small_cell(name, 13)
    driver = cell.driver
    st = driver.setup(cell, base_state(name))
    win = driver.window(st, SECONDS[name], False)
    driver.release(st, win)
    sound, control = driver.checks(st, win), driver.control(st, win)
    assert any(control[k] > cell.limits[k] for k in control), control
    assert all(sound[k] <= cell.limits[k] for k in sound), sound


@pytest.mark.parametrize("make", [
    lambda g: data.gauge_field(g, 2), lambda g: data.links(g),
    lambda g: data.axis_constant_field(g, 2), lambda g: data.vector_field(g, 2)])
def test_same_seed_same_data(make):
    big = 2**31 + 12345
    a, b = make(data.rng(big, 3)), make(data.rng(big, 3))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, make(data.rng(big + 1, 3)))


def test_random_links_are_su3():
    u = data.random_su3(data.rng(5, 0), 64).astype(np.complex128)
    eye = np.einsum("nij,nkj->nik", u, u.conj())
    assert np.abs(eye - np.eye(3)).max() < 1e-5
    assert np.abs(np.linalg.det(u) - 1).max() < 1e-5
