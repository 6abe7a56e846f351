"""Crispy for TPU slices: before launching an (arch x shape) job, profile
five reduced-depth compiles on this CPU host, extrapolate per-device HBM to
the full depth, and pick the cheapest feasible slice from the TPU catalog.

  PYTHONPATH=src python examples/mesh_advisor.py --arch deepseek-7b
"""
import argparse
import dataclasses

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.hbm_planner import HBMPlanner
from repro.launch.mesh import make_mesh

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

    planner = HBMPlanner(leeway=0.05)
    rep = planner.plan(cfg, shape, mesh, run=run, anchor_layers=12)
    print(f"arch={cfg.name} layers ladder={rep.ladder}")
    print(f"per-device bytes at ladder: "
          f"{[f'{m / 2**20:.1f}MiB' for m in rep.per_dev_bytes]}")
    print(f"OLS: slope={rep.model.slope / 2**20:.2f} MiB/layer, "
          f"intercept={rep.model.intercept / 2**20:.1f} MiB, "
          f"R2={rep.model.r2:.5f} "
          f"({'PASS' if rep.model.confident else 'fallback'})")
    print(f"extrapolated to {cfg.n_layers} layers: "
          f"{rep.predicted_per_dev_gib:.3f} GiB/device "
          f"-> aggregate requirement {rep.requirement_gib:.2f} GiB")
    sel = rep.selection
    print(f"selected: {sel.config.name} "
          f"({sel.config.total_mem_gib:.0f} GiB HBM, "
          f"${sel.config.usd_per_hour:.2f}/h; "
          f"{sel.feasible_count} feasible configs"
          f"{'; fell back' if sel.fell_back else ''})")
    # ground truth check
    truth = planner.profile_memory(cfg, shape, mesh, run)
    err = abs(rep.predicted_per_dev_gib * GiB - truth) / truth
    print(f"ground-truth full compile: {truth / GiB:.3f} GiB/device "
          f"(extrapolation error {err:.2%})")


if __name__ == "__main__":
    main()
