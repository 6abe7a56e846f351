"""Planner accuracy: one `AllocationPipeline` request per architecture,
profiled by `HBMPlanner.profile_at` over reduced-depth compiles; the
fitted memory at the full depth is compared against the ground-truth full
compile. The at-scale Table I row: 'did Crispy get the memory requirement
right without running the job'."""
from __future__ import annotations

import dataclasses
import time

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.catalog import tpu_catalog
from repro.core.hbm_planner import HBMPlanner, TPU_OVERHEAD_GIB
from repro.core.history import ExecutionHistory
from repro.launch.mesh import make_mesh
from repro.pipeline import AllocationPipeline, PipelineRequest

ARCHS_TO_CHECK = ["deepseek-7b", "chatglm3-6b", "rwkv6-7b", "whisper-small"]


def run(verbose=True):
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=256,
                                global_batch=4)
    run_cfg = RunConfig(attn_impl="full", remat="nothing",
                        compute_dtype="float32", microbatches=1)
    planner = HBMPlanner()
    pipe = AllocationPipeline(tpu_catalog(), ExecutionHistory(),
                              overhead_per_node_gib=TPU_OVERHEAD_GIB)
    rows = []
    for arch in ARCHS_TO_CHECK:
        cfg = get_arch(arch).reduced(n_layers=24, d_model=128,
                                     vocab_size=512)
        tr = pipe.run(PipelineRequest(
            f"{cfg.name}:{shape.name}",
            planner.profile_at(cfg, shape, mesh, run_cfg),
            full_size=cfg.n_layers, sizes=HBMPlanner.ladder(cfg, 10)))
        fit = tr.plan.fit
        truth = planner.profile_memory(cfg, shape, mesh, run_cfg)
        pred = fit.predict(cfg.n_layers)
        rel = abs(pred - truth) / truth
        rows.append({"arch": arch, "r2": fit.r2, "candidate": fit.candidate,
                     "confident": fit.confident, "rel_err": rel,
                     "wall_s": tr.wall_s})
        if verbose:
            print(f"{arch:18s} {fit.candidate:10s} R2={fit.r2:8.5f} "
                  f"gate={'PASS' if fit.confident else 'fallback'} "
                  f"pred={pred / 2**20:8.1f}MiB truth={truth / 2**20:8.1f}MiB "
                  f"err={rel:6.2%} profile={tr.wall_s:5.1f}s")
    return rows


def main():
    t0 = time.monotonic()
    rows = run()
    wall = time.monotonic() - t0
    max_err = max(r["rel_err"] for r in rows if r["confident"])
    n_pass = sum(r["confident"] for r in rows)
    print(f"planner_validation,{wall / max(len(rows),1) * 1e6:.0f},"
          f"max_rel_err={max_err:.4f};gate_pass={n_pass}/{len(rows)}")


if __name__ == "__main__":
    main()
