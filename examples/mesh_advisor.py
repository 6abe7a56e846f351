"""Crispy for TPU slices: before launching an (arch x shape) job, profile
reduced-depth compiles on this host, extrapolate the job's HBM to the full
depth, and pick the cheapest feasible slice from the TPU catalog — one
request through `AllocationPipeline`, with the planner as its profile
source.

  PYTHONPATH=src python examples/mesh_advisor.py --arch deepseek-7b
"""
import argparse
import dataclasses

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.catalog import tpu_catalog
from repro.core.hbm_planner import HBMPlanner, TPU_OVERHEAD_GIB
from repro.core.history import ExecutionHistory
from repro.launch.mesh import make_mesh
from repro.pipeline import AllocationPipeline, PipelineRequest

GiB = 1024 ** 3


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="width scale of the profiled job (1.0 = demo size)")
    args = ap.parse_args(argv)

    # demo-sized job so the advisor runs in seconds on CPU; the same code
    # path drives full configs under the dry-run device flag
    cfg = get_arch(args.arch).reduced(
        d_model=int(256 * args.scale), n_layers=32, vocab_size=2048,
        d_ff=int(512 * args.scale))
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=512,
                                global_batch=8)
    run = RunConfig(attn_impl="blocked", remat="boundaries",
                    compute_dtype="bfloat16", microbatches=2)
    mesh = make_mesh((1, 1), ("data", "model"))

    planner = HBMPlanner()
    tr = AllocationPipeline(
        tpu_catalog(), ExecutionHistory(),
        overhead_per_node_gib=TPU_OVERHEAD_GIB, leeway=0.05).run(
            PipelineRequest(f"{cfg.name}:{shape.name}",
                            planner.profile_at(cfg, shape, mesh, run),
                            full_size=cfg.n_layers,
                            sizes=planner.ladder(cfg, 12)))
    fit = tr.plan.fit
    print(f"arch={cfg.name} layers ladder={[int(s) for s in tr.sizes]}")
    print(f"bytes at ladder: {[f'{m / 2**20:.1f}MiB' for m in tr.mems]}")
    print(f"fit: {fit.candidate}, R2={fit.r2:.5f} "
          f"({'PASS' if fit.confident else 'fallback'})")
    print(f"extrapolated to {cfg.n_layers} layers: requirement "
          f"{tr.requirement_gib:.3f} GiB ({tr.source})")
    sel = tr.selection
    print(f"selected: {sel.config.name} "
          f"({sel.config.total_mem_gib:.0f} GiB HBM, "
          f"${sel.config.usd_per_hour:.2f}/h; "
          f"{sel.feasible_count} feasible configs"
          f"{'; fell back' if sel.fell_back else ''})")
    # ground truth check, without the leeway the requirement carries
    truth = planner.profile_memory(cfg, shape, mesh, run)
    pred = fit.predict(cfg.n_layers)
    print(f"ground-truth full compile: {truth / GiB:.3f} GiB/device "
          f"(extrapolation error {abs(pred - truth) / truth:.2%})")


if __name__ == "__main__":
    main()
