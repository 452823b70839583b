"""Training launcher.

Two modes:
  * real run (CPU container): reduced variant of any arch on the
    synthetic LM — ``--reduced`` (the default here, since full configs
    need the real pods);
  * full configs are exercised via ``repro.launch.dryrun``.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
      --reduced --steps 200 --batch 8 --seq 128 --ckpt /tmp/ckpt.npz
"""
from __future__ import annotations

import argparse
import dataclasses


from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.data import lm_batches
from repro.training import save_checkpoint, train
from repro.training.optimizer import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, layers=args.layers, d_model=args.d_model,
                      vocab=args.vocab)
        cfg = dataclasses.replace(cfg, dtype="float32")
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("frontend-stub archs: use examples/ drivers")

    batches = lm_batches(cfg.vocab_size, args.batch, args.seq,
                         args.steps, seed=args.seed)
    params, losses = train(cfg, batches, steps=args.steps,
                           opt_cfg=AdamWConfig(lr=args.lr), seed=args.seed)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    if args.ckpt:
        save_checkpoint(args.ckpt, params, step=args.steps)
        print("saved", args.ckpt)


if __name__ == "__main__":
    main()
