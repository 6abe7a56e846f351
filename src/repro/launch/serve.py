"""Serving launcher: drive the continuous-batching engine from the CLI.

Runs the published widths of `--arch`; `--reduced` swaps in the tiny
same-family config (CPU).

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
      --requests 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import RunConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    run = RunConfig(attn_impl="full", remat="nothing",
                    compute_dtype="float32",
                    kv_cache_dtype="int8" if args.int8_kv else "compute")
    model = Model(cfg, run)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServeEngine(model, params, slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    for rid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size, size=3).tolist()
        engine.submit(Request(rid, prompt=prompt, max_new_tokens=args.max_new,
                              temperature=args.temperature))
    done = engine.run()
    wall = time.monotonic() - t0
    toks = sum(len(r.out_tokens) for r in done)
    lats = [r.finished_at - r.submitted_at for r in done]
    print(f"[serve] {cfg.name}: {len(done)} requests, {toks} tokens in "
          f"{wall:.2f}s ({toks / wall:.1f} tok/s, slots={args.slots}, "
          f"kv={'int8' if args.int8_kv else run.compute_dtype})")
    print(f"[serve] latency p50={np.percentile(lats, 50):.2f}s "
          f"p95={np.percentile(lats, 95):.2f}s")
    return done


if __name__ == "__main__":
    main()
