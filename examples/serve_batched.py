"""Batched serving example: prefill a prompt batch, decode N tokens.

    PYTHONPATH=src python examples/serve_batched.py --arch yi-6b --tokens 16
"""
import argparse

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config
from repro.models import registry
from repro.serve.engine import ServeConfig, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    api = registry.get(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(
        cfg, params,
        ServeConfig(max_len=args.prompt_len + args.tokens + 8,
                    temperature=args.temperature),
    )
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32
    )
    extras = {}
    if cfg.n_patches:
        extras["patches"] = jax.random.normal(
            jax.random.PRNGKey(9), (args.batch, cfg.n_patches, cfg.d_model)
        )
    if cfg.is_encoder_decoder:
        extras["frames"] = jax.random.normal(
            jax.random.PRNGKey(10), (args.batch, cfg.encoder_len, cfg.d_model)
        )
    out = engine.generate(prompts, args.tokens, extras=extras or None)
    print(f"arch {args.arch}: generated {out.shape} "
          f"(batch {args.batch}, {args.tokens} new tokens each)")
    print("continuations:")
    for row in out[:, args.prompt_len:]:
        print("  ", row.tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
