"""End-to-end training driver: train a reduced LM for a few hundred steps
on CPU with checkpointing, resume, and loss tracking.

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-4b --steps 300
    PYTHONPATH=src python examples/train_lm.py --arch granite-moe-1b-a400m --steps 200
"""
import argparse

from repro.configs import ALL_ARCHS, get_config
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    print(f"arch {args.arch} (reduced: {cfg.n_layers}L d{cfg.d_model}, "
          f"~{cfg.n_params() / 1e6:.1f}M params)")
    tcfg = TrainConfig(
        steps=args.steps, seq_len=args.seq_len, global_batch=args.batch,
        checkpoint_dir=args.checkpoint_dir, log_every=20,
        opt=AdamWConfig(peak_lr=args.lr, warmup_steps=args.steps // 10,
                        total_steps=args.steps),
    )
    out = train(cfg, tcfg)
    first, last = out["losses"][0], out["losses"][-1]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'LEARNED' if last < first - 0.2 else 'check hyperparams'})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
