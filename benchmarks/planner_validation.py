"""§Planner: Crispy HBM-planner extrapolation accuracy — profile five
reduced-depth compiles, extrapolate per-device memory to the full depth,
compare against the ground-truth full compile. The at-scale Table I row:
'did Crispy get the memory requirement right without running the job'."""
from __future__ import annotations

import dataclasses
import time

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.hbm_planner import HBMPlanner
from repro.launch.mesh import make_mesh

GiB = 1024 ** 3

ARCHS_TO_CHECK = ["deepseek-7b", "chatglm3-6b", "rwkv6-7b", "whisper-small"]


def run(verbose=True):
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=256,
                                global_batch=4)
    run_cfg = RunConfig(attn_impl="full", remat="nothing",
                        compute_dtype="float32", microbatches=1)
    planner = HBMPlanner(leeway=0.0)
    rows = []
    for arch in ARCHS_TO_CHECK:
        cfg = get_arch(arch).reduced(n_layers=24, d_model=128,
                                     vocab_size=512)
        rep = planner.plan(cfg, shape, mesh, run=run_cfg, anchor_layers=10,
                           select=False)
        truth = planner.profile_memory(cfg, shape, mesh, run_cfg)
        pred = rep.predicted_per_dev_gib * GiB
        rel = abs(pred - truth) / truth
        rows.append({"arch": arch, "r2": rep.model.r2,
                     "confident": rep.model.confident,
                     "rel_err": rel, "wall_s": rep.profile_wall_s})
        if verbose:
            print(f"{arch:18s} R2={rep.model.r2:8.5f} "
                  f"gate={'PASS' if rep.model.confident else 'fallback'} "
                  f"pred={pred / 2**20:8.1f}MiB truth={truth / 2**20:8.1f}MiB "
                  f"err={rel:6.2%} profile={rep.profile_wall_s:5.1f}s")
    return rows


def main():
    t0 = time.monotonic()
    rows = run()
    wall = time.monotonic() - t0
    import numpy as np
    max_err = max(r["rel_err"] for r in rows if r["confident"])
    n_pass = sum(r["confident"] for r in rows)
    print(f"planner_validation,{wall / max(len(rows),1) * 1e6:.0f},"
          f"max_rel_err={max_err:.4f};gate_pass={n_pass}/{len(rows)}")


if __name__ == "__main__":
    main()
