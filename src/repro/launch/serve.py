"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --batch 8 --tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config
from repro.models import registry
from repro.serve.engine import ServeConfig, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ALL_ARCHS, required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    api = registry.get(cfg)
    params = api.init(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(
        cfg, params,
        ServeConfig(max_len=args.prompt_len + args.tokens + 8,
                    temperature=args.temperature, seed=args.seed),
    )
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32
    )
    extras = {}
    if cfg.n_patches:
        extras["patches"] = jax.random.normal(
            jax.random.PRNGKey(9), (args.batch, cfg.n_patches, cfg.d_model))
    if cfg.is_encoder_decoder:
        extras["frames"] = jax.random.normal(
            jax.random.PRNGKey(10), (args.batch, cfg.encoder_len, cfg.d_model))
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.tokens, extras=extras or None)
    dt = time.perf_counter() - t0
    print(f"{args.arch}: {out.shape[0]}x{args.tokens} tokens in {dt:.2f}s "
          f"({out.shape[0] * args.tokens / dt:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
