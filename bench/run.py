"""Run one benchmark cell on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints earlier ``{"info": ...}`` lines (device, versions, plan, set-up and
compile seconds, persistent-cache hits, compiles inside the window, peak
device memory), then one JSON result line last: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and ``checks`` (each compared number beside its limit), which
also close standard error.  With no TPU, or fewer chips than the cell asks
for, it exits non-zero before any set-up and prints no result.
"""
from __future__ import annotations

import time

STARTED_S = time.perf_counter()

import argparse  # noqa: E402
import importlib.metadata  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is bench/, whose trace.py would shadow the
# standard library's: import the benchmark as the package it is instead
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))  # the system under test

# JAX's persistent compilation cache lives in the checkout, at a fixed path
# (the path is part of every key), with no size cap: a capped cache evicts
# this cell's programs between runs.  libtpu writes no log files.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
os.environ["TPU_LOG_DIR"] = "disabled"


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, peaks

    benchmark = harness.load_benchmark()
    cell = harness.load_cell(args.workload, args.seed)
    chips = int(cell.workload["chips"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.stderr(f"bench: no TPU (jax found {devices[0].platform!r}); "
                       "the benchmark only runs on the chip")
        return 2
    if len(devices) < chips:
        harness.stderr(f"bench: {cell.name} needs {chips} chips, jax found "
                       f"{len(devices)}")
        return 2
    devices = devices[:chips]
    chip_peaks = peaks.for_device_kind(devices[0].device_kind)

    from repro.compile_cache import enable_compile_cache

    harness.info(device_kind=devices[0].device_kind, device_count=len(devices),
                 jax=jax.__version__, jaxlib=_version("jaxlib"),
                 libtpu=_version("libtpu"), cell=cell.name, seed=args.seed,
                 seconds=args.seconds, trace=args.trace,
                 compile_cache=enable_compile_cache(),
                 peaks_source=chip_peaks.source)
    jax.config.update("jax_compilation_cache_max_size", -1)
    result = harness.run_cell(
        cell, args.seconds, bool(args.trace), devices=devices, peaks=chip_peaks,
        benchmark=benchmark, started_s=STARTED_S, clock=harness.CompileClock())
    harness.report_checks(result, harness.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
