"""Serving launcher — offload mode (the paper's deployment) or plain
on-device batched decode, on a reduced arch (CPU container).

Example (paper mode, LFU + speculative prefetch):
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
      --cache-slots 4 --policy lfu --prefetch spec --tokens 64
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serving import OffloadServer, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--mode", choices=["offload", "device"], default="offload")
    ap.add_argument("--policy", default="lru")
    ap.add_argument("--prefetch", default=None,
                    choices=[None, "spec", "markov", "learned"])
    ap.add_argument("--cache-slots", type=int, default=4)
    ap.add_argument("--quant", default="none", choices=["none", "int8"])
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch), layers=args.layers,
                  d_model=args.d_model)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = tf.init_params(cfg, jax.random.PRNGKey(args.seed))
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]

    if args.mode == "offload":
        if not cfg.is_moe:
            raise SystemExit(f"{args.arch} has no experts to offload")
        srv = OffloadServer(params, cfg, cache_slots=args.cache_slots,
                            policy=args.policy, prefetch=args.prefetch,
                            quant=args.quant, overlap=args.overlap)
        out = srv.complete(prompt, max_new=args.tokens)
        print("tokens:", out)
        for k, v in srv.stats().items():
            print(f"  {k:22s} {v}")
        print(srv.render_trace(layer=min(1, cfg.num_layers - 1)))
    else:
        eng = ServingEngine(params, cfg, cache_len=len(prompt) + args.tokens)
        outs = eng.generate_batch([prompt, prompt[::-1]], max_new=args.tokens)
        for o in outs:
            print("tokens:", o)


if __name__ == "__main__":
    main()
