"""Paper Fig. 7 analog: strong scaling of VersionX over (host, device) meshes.

Historically each point ran in its own child process (device count locks at
first jax init, so a fresh process per count).  Now the whole curve is ONE
multi-controller dry-run launch through ``repro.launch.dryrun --su3-fig7``:

  * one launch spawns N identical controller processes over a forced device
    pool covering ``max(device_counts)``;
  * inside each controller every point slices its mesh from that pool via
    ``repro.launch.mesh.MeshSpec`` — the real ``build_plan`` (host, device)
    path with per-host first-touch init, not a bespoke benchmark harness;
  * the launcher byte-compares every point's result lattice against the
    single-host reference across ALL controllers and fails the launch on
    divergence.

Both placement policies are measured — the paper's with/without-empty-
constructor pair.  Row names stay ``fig7_{placement}_d{n}`` so the
``scripts/bench_diff.py`` trajectory is unbroken.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def run(
    L: int = 8,
    device_counts: tuple[int, ...] = (1, 2, 4),
    hosts: int = 2,
    controllers: int = 2,
) -> list[dict]:
    """One multi-controller launch; returns controller 0's benchmark rows.

    Args:
        L: lattice extent per point.
        device_counts: mesh sizes to sweep (each sliced from one pool).
        hosts: host-axis size of each point's MeshSpec (capped at the
            point's device count; d1 stays the legacy single-host mesh).
        controllers: identical controller processes to launch and
            divergence-check.

    Returns:
        Rows named ``fig7_{placement}_d{n}`` (error row on launch failure).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["JAX_PLATFORMS"] = "cpu"  # a CPU simulation: never takes the chip
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun", "--su3-fig7",
        "--L", str(L),
        "--device-counts", ",".join(str(n) for n in device_counts),
        "--hosts", str(hosts),
        "--controllers", str(controllers),
    ]
    out = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=900, cwd=ROOT,
    )
    if out.returncode != 0:
        return [{
            "name": "fig7_launch_error",
            "error": (out.stderr or out.stdout)[-300:],
        }]
    # rows are the last JSON line on stdout (workers' chatter goes to stderr)
    last = out.stdout.strip().splitlines()[-1]
    return json.loads(last)


if __name__ == "__main__":
    for r in run():
        print(r)
