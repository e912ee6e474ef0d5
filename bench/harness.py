"""Runs one cell: finds its files by name, drives the window, prints the result.

Everything that belongs to one cell, configuration, traffic driver,
reference or per-layer metric is a file of its own under ``bench/``, found
by the name ``BENCHMARK.json`` and the cell's file give it:

* ``workloads/<cell>.json``   configuration, traffic and driver names, the
                              traffic parameters, chips, limits of the check;
* ``configs/<config>.json``   the deployment's sizes, as run;
* ``drivers/<driver>.py``     one traffic kind: ``setup``, ``window``,
                              ``release``, ``checks``, ``control``;
* ``references/<ref>.py``     the plain reference the checks compare with;
* ``metrics/<metric>.py``     one per-layer metric: ``read(record)``.

A driver module defines::

    setup(cell, previous=None) -> state        # data, program, warm shapes;
                                               # reuses ``previous``'s program
                                               # objects where given
    window(state, seconds, trace) -> Window    # the measured closed loop
    release(state, window) -> None             # drop device state
    checks(state, window) -> {name: value}     # against the reference
    control(state, window) -> {name: value}    # the same, with the reference
                                               # in lower precision in the
                                               # program's place

In a traced window (``trace=True``) a driver wraps each call into the
program in ``annotations(True)(name)``, a ``jax.profiler.TraceAnnotation``
named ``bench.*``, so the trace can say what the host was doing in each idle
gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Callable

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    config: dict[str, Any]
    workload: dict[str, Any]
    seed: int
    base: pathlib.Path = BENCH

    @property
    def params(self) -> dict[str, Any]:
        return self.workload["params"]

    @property
    def limits(self) -> dict[str, float]:
        return self.workload["limits"]

    def module(self, kind: str, name: str) -> Any:
        return load_module(self.base / kind / f"{name}.py")

    @property
    def driver(self) -> Any:
        return self.module("drivers", self.workload["driver"])

    @property
    def reference(self) -> Any:
        return self.module("references", self.config["reference"])


@dataclasses.dataclass
class Window:
    """What one measured window produced."""

    seconds: float
    attempted: int
    failed: int
    metrics: dict[str, float]  # end-to-end metrics the driver measured
    counters: dict[str, Any]  # counts and spans the per-layer metrics read
    kept: dict[str, Any]  # outputs kept for the comparison


@dataclasses.dataclass
class Record:
    """What a per-layer metric reads: the reduced device trace of the traced
    window, the driver's counters and spans from it, and the yardstick."""

    cell: Cell
    trace: Any  # bench.trace.Reduced, or None where nothing was traced
    counters: dict[str, Any]
    peaks: Any  # bench.peaks.Peaks


@functools.cache
def load_module(path: pathlib.Path) -> Any:
    """Import one file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = f"bench_{path.parent.name}_{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up there
    spec.loader.exec_module(module)
    return module


def load_json(path: pathlib.Path) -> dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: pathlib.Path = ROOT) -> dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def load_cell(name: str, seed: int, base: pathlib.Path = BENCH) -> Cell:
    workload = load_json(base / "workloads" / f"{name}.json")
    config = load_json(base / "configs" / f"{workload['config']}.json")
    return Cell(name=name, config=config, workload=workload, seed=seed, base=base)


def _applies(entry: dict[str, Any], cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in e2e_names


def metrics_for(benchmark: dict[str, Any], cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries that ``cell`` reports."""
    e2e = [m for m in benchmark["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"] if _applies(m, cell, names)]
    return e2e, per_layer


class CompileClock:
    """Seconds JAX spends compiling, compiles, and persistent-cache hits,
    read from JAX's monitoring events (listeners live for the process)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def annotations(trace: bool) -> Callable[[str], contextlib.AbstractContextManager]:
    """``jax.profiler.TraceAnnotation`` in a traced window, else a no-op."""
    if trace:
        import jax

        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


def info(**fields: Any) -> None:
    """One earlier line of the run, as JSON on standard output."""
    print(json.dumps({"info": fields}, default=str), flush=True)


def traced_window(cell: Cell, state: Any, seconds: float) -> tuple[Window, Any]:
    """A short window under the profiler; returns it and its reduced trace."""
    import tempfile

    import jax

    from bench import trace as bench_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        with jax.profiler.trace(tdir, profiler_options=opts):
            win = cell.driver.window(state, seconds, True)
        reduced = bench_trace.reduce_dir(tdir)
    return win, reduced


def run_cell(cell: Cell, seconds: float, trace: bool, *, devices: list,
             peaks: Any, benchmark: dict[str, Any], started_s: float,
             clock: CompileClock | None = None, previous: Any = None) -> dict[str, Any]:
    """Set up, measure, check; returns the result line's object.
    ``previous`` hands an earlier state's program objects to the set-up."""
    e2e, per_layer = metrics_for(benchmark, cell.name)
    driver = cell.driver
    device = devices[0]
    c0 = clock.snapshot() if clock else (0.0, 0, 0)
    state = driver.setup(cell, previous)
    setup_s = time.perf_counter() - started_s
    c1 = clock.snapshot() if clock else (0.0, 0, 0)
    win = driver.window(state, seconds, False)
    c2 = clock.snapshot() if clock else (0.0, 0, 0)
    trace_win = reduced = None
    if trace:
        trace_win, reduced = traced_window(
            cell, state, float(cell.workload["trace_seconds"]))
    stats = device.memory_stats() or {}
    info(setup_s=setup_s, compile_s=c1[0] - c0[0], compiles=c1[1] - c0[1],
         cache_hits_in_setup=c1[2] - c0[2],
         compiles_in_window=c2[1] - c1[1],
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         window_s=win.seconds, attempted=win.attempted, failed=win.failed)
    if trace_win is not None:
        trace_win.kept.clear()  # the check reads the measured window's outputs
    driver.release(state, win)
    gc.collect()
    t0 = time.perf_counter()
    values = driver.checks(state, win)
    info(check_s=time.perf_counter() - t0)
    checks = {name: {"value": float(v), "limit": float(cell.limits[name])}
              for name, v in values.items()}
    correct = (win.failed == 0 and win.attempted > 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))

    if trace:
        record = Record(cell=cell, trace=reduced, counters=trace_win.counters,
                        peaks=peaks)
        metrics = {}
        for m in per_layer:
            value = cell.module("metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        measured = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(measured[m["name"]]), "unit": m["unit"]}
                   for m in e2e}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices),
           "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result: dict[str, Any] = {"correct": correct, "attempted": win.attempted,
                              "failed": win.failed, "metrics": metrics,
                              "device": dev}
    if trace:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result


def report_checks(result: dict[str, Any], out: Callable[[str], None]) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in result["checks"].items():
        out(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out(f"correct = {result['correct']} (attempted {result['attempted']}, "
        f"failed {result['failed']})")


def stderr(line: str) -> None:
    print(line, file=sys.stderr, flush=True)
