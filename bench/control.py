"""Readings that set a cell's check limits: the program's, and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds 1,2,...,12 --control-seeds 13,14,15

In one process, for each seed: the cell's set-up (program objects reused
across seeds, data made anew), a short window at the cell's own load, and
its checks against the plain reference.  For each control seed the same
window runs, and the checks are read again with the reference computed in
the precision below the configuration's put in the program's place
(bfloat16 storage for float32): the control has to fail them.  One JSON line
per seed; the last line gives the largest program reading and the smallest
control reading of each check.  Like ``run.py`` it needs the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
os.environ["TPU_LOG_DIR"] = "disabled"


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def readings(name: str, seconds: float, program_seeds: list[int],
             control_seeds: list[int]) -> dict[str, dict[str, float]]:
    """Run the seeds; returns {check: {"program_max", "control_min"}}."""
    from bench import harness

    previous = None
    program: dict[str, list[float]] = {}
    control: dict[str, list[float]] = {}
    for seed in program_seeds + [s for s in control_seeds if s not in program_seeds]:
        cell = harness.load_cell(name, seed)
        driver = cell.driver
        state = driver.setup(cell, previous)
        win = driver.window(state, seconds, False)
        driver.release(state, win)
        row = {"seed": seed, "attempted": win.attempted, "failed": win.failed}
        if seed in program_seeds:
            row["program"] = driver.checks(state, win)
            for k, v in row["program"].items():
                program.setdefault(k, []).append(v)
        if seed in control_seeds:
            row["control"] = driver.control(state, win)
            for k, v in row["control"].items():
                control.setdefault(k, []).append(v)
        print(json.dumps(row), flush=True)
        previous = state
        gc.collect()
    return {k: {"program_max": max(program.get(k, [float("nan")])),
                "control_min": min(control.get(k, [float("nan")]))}
            for k in set(program) | set(control)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; the readings are taken on the chip", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    summary = readings(args.workload, args.seconds, args.program_seeds,
                       args.control_seeds)
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
