"""Batched SU3 lattice serving through the SU3Service front door.

Each request carries its own (A, B) lattice pair.  Requests flow through the
dynamic batcher ((L, k) buckets, warm-size padding, admission control) into a
warm pool of vmapped ExecutionPlan runners — no per-request compilation, no
per-layout wiring, and (with ``--bf16``) bf16-storage / f32-accumulate plans
that stream half the HBM bytes.  The plan tuple (layout, kernel, tile) and
the default chain depth come from the persistent autotune cache, so the
first run on a device measures once and every later process starts tuned.

    PYTHONPATH=src python examples/serve_lattices.py --batch 8 --L 4 --chain 3
    PYTHONPATH=src python examples/serve_lattices.py --batch 8 --bf16
    PYTHONPATH=src python examples/serve_lattices.py --batch 8 --autotune
"""
import argparse
import time

import jax
import numpy as np

from repro.serve.su3 import BatcherConfig, ServiceConfig, SU3Service, request_flops


def _random_requests(batch: int, n_sites: int, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (batch, n_sites, 4, 3, 3, 2))
    b = jax.random.normal(kb, (batch, 4, 3, 3, 2))
    return jax.lax.complex(a[..., 0], a[..., 1]), jax.lax.complex(b[..., 0], b[..., 1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8, help="independent user lattices")
    ap.add_argument("--L", type=int, default=4)
    ap.add_argument("--chain", type=int, default=0,
                    help="multiplies chained per request "
                         "(0 = the autotuned fused depth from the cache)")
    ap.add_argument("--tile", type=int, default=0,
                    help="explicit tile; overrides --autotune (no point paying "
                         "the sweep just to discard its tile)")
    ap.add_argument("--bf16", action="store_true",
                    help="bf16-storage / f32-accumulate serving plans")
    ap.add_argument("--autotune", action="store_true",
                    help="build the pool through the persistent autotune cache "
                         "(first run measures once, later runs start tuned)")
    args = ap.parse_args()

    svc = SU3Service(ServiceConfig(
        dtype="bfloat16" if args.bf16 else "float32",
        accum_dtype="float32" if args.bf16 else "",
        autotune=args.autotune and not args.tile,
        tile=args.tile,
        batcher=BatcherConfig(
            max_batch=max(8, args.batch),
            warm_batch_sizes=(1, 2, 4, 8, max(8, args.batch)),
            max_queue_depth=4 * max(8, args.batch),
        ),
    ))

    n_sites = args.L**4
    a, b = _random_requests(args.batch, n_sites)
    k = args.chain or None  # None => tuned_fused_k (autotune) / service default

    # Warm pass: pay plan build + jit outside the timed window (a real
    # deployment does this at rollout, not inside a user's request).
    ids = [svc.submit(a[i], b[i], k=k) for i in range(args.batch)]
    svc.run_until_drained()
    resolved_k = args.chain or svc.default_k_for(args.L)
    for rid in ids:
        svc.pop_result(rid)
    svc.metrics.reset()

    t0 = time.perf_counter()
    ids = [svc.submit(a[i], b[i], k=k) for i in range(args.batch)]
    served = svc.run_until_drained()
    wall = time.perf_counter() - t0
    c = [svc.pop_result(rid) for rid in ids]

    ecfg = svc.runner_for(args.L).cfg
    print(f"plan: layout={ecfg.layout.value} variant={ecfg.variant} "
          f"tile={ecfg.tile} dtype={ecfg.dtype}"
          + (f" accum={ecfg.accum_dtype}" if ecfg.is_mixed_precision else "")
          + f" chain_k={resolved_k}")
    flops = args.batch * request_flops(n_sites, resolved_k)
    print(f"served {served} lattices (L={args.L}, {n_sites} sites, "
          f"chain={resolved_k}) on {svc.runner_for(args.L).n_devices} device(s) "
          f"in {wall*1e3:.1f} ms -> {flops / wall / 1e9:.2f} GF/s aggregate")
    snap = svc.metrics.snapshot()
    print(f"metrics: p50={snap['latency_p50_ms']} ms "
          f"p99={snap['latency_p99_ms']} ms "
          f"occupancy={snap['mean_batch_occupancy']} "
          f"live/batch={snap['mean_live_batch']} "
          f"dispatches={snap['dispatches']}")
    print("sample C[0,0,0]:", np.asarray(jax.device_get(c[0]))[0, 0, 0])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
