"""Training launcher.

CPU-runnable end to end with --reduced (the quickstart path); at full scale
the same flags drive the dry-run compile of the exact production job. The
Crispy HBM planner can be consulted first (--plan) to pick the mesh.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
      --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import RunConfig
from repro.data.pipeline import ShardedLoader, SyntheticLMDataset
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.train.loop import LoopConfig, train_loop
from repro.train.step import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    run = RunConfig(attn_impl="full" if args.seq <= 512 else "blocked",
                    remat="nothing", compute_dtype="float32",
                    microbatches=args.microbatches,
                    grad_compression=args.grad_compression)
    model = Model(cfg, run)
    acfg = AdamWConfig(lr=args.lr)
    state = init_train_state(model, jax.random.PRNGKey(args.seed), acfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(
        state.params))
    print(f"[launch] {cfg.name} ({'reduced' if args.reduced else 'full'}): "
          f"{n_params / 1e6:.2f}M params")

    step_fn = jax.jit(make_train_step(model, acfg, None,
                                      total_steps=args.steps),
                      donate_argnums=(0,))
    ds = SyntheticLMDataset(cfg.vocab_size, args.seed)
    loader = ShardedLoader(ds, args.batch, args.seq)

    def wrapped(state, batch):
        if cfg.family == "vlm":
            batch = dict(batch, media=np.zeros(
                (args.batch, cfg.cross_attn.n_media_tokens, cfg.d_model),
                np.float32))
        if cfg.family == "audio":
            batch = dict(batch, frames=np.zeros(
                (args.batch, cfg.encdec.enc_len, cfg.d_model), np.float32))
        return step_fn(state, batch)

    lcfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=10)
    state, report = train_loop(state, wrapped, loader, lcfg)
    print(f"[done] final loss {report.losses[-1]:.4f} "
          f"(first {report.losses[0]:.4f}) over {report.final_step} steps; "
          f"stragglers: {len(report.stragglers)}")
    return report


if __name__ == "__main__":
    main()
