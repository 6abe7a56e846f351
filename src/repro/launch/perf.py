"""§Perf hillclimb driver: run named variants of the three chosen cells,
record the roofline terms per variant into experiments/perf/.

Each variant encodes an explicit hypothesis (see EXPERIMENTS.md §Perf);
the 256-chip count is held constant — mesh shape, remat policy, microbatch
count, MoE dispatch and gradient compression are the knobs.

  PYTHONPATH=src python -m repro.launch.perf --cell ds7b --variant tp8
  PYTHONPATH=src python -m repro.launch.perf --all
"""

import argparse
import dataclasses
import json
import os
import time
import traceback

import jax

from repro.configs import SHAPES, get_arch
from repro.configs.base import MeshConfig, RunConfig
from repro.launch.dryrun import run_cell
from repro.launch.mesh import make_mesh
from repro.launch.presets import preset_run


def mesh_of(shape, axes=("data", "model")):
    return make_mesh(shape, axes)


def ds7b_variants():
    cfg = get_arch("deepseek-7b")
    shape = SHAPES["train_4k"]

    def base_run(mesh_shape):
        mc = MeshConfig(mesh_shape, ("data", "model"))
        return preset_run(cfg, shape, mc)

    return cfg, shape, [
        # (name, mesh shape, run)
        ("baseline", (16, 16), base_run((16, 16))),
        # H1: remat 'dots' removes the recompute forward's TP all-reduces
        # (1/3 of activation-collective volume) at +stash memory
        ("remat_dots", (16, 16), base_run((16, 16)).with_(remat="dots")),
        # H2: TP=8/DP=32 — TP all-reduce volume per device is
        # tokens-per-device * d * L; doubling DP halves it; params/shard
        # 2x (3.5 GiB bf16-equiv, fits)
        ("tp8", (32, 8), base_run((32, 8))),
        # H3: TP=4/DP=64 + dots — collective down ~4x vs baseline, compute
        # unchanged; expect memory-bound
        ("tp4_dots", (64, 4), base_run((64, 4)).with_(remat="dots")),
        # H4: H3 + bf16 gradient all-reduce (halves the DP gradient wire)
        ("tp4_dots_gcomp", (64, 4),
         base_run((64, 4)).with_(remat="dots", grad_compression=True)),
        # H5: H3 exceeded the 16 GiB budget (f32 grads + f32 params at
        # TP=4). bf16 params/moments/accumulator + ZeRO-1 master brings it
        # back under while keeping the collective win
        ("tp4_dots_bf16", (64, 4),
         base_run((64, 4)).with_(remat="dots", param_dtype="bfloat16",
                                 moment_dtype="bfloat16",
                                 accum_dtype="bfloat16")),
        # H6: budget-compliant TP=4: keep remat=boundaries (no dots stash);
        # collective gets the recompute psums back (~+33%) but memory/dev
        # drops below 16 GiB with bf16 params+accum
        ("tp4_bound_bf16", (64, 4),
         base_run((64, 4)).with_(param_dtype="bfloat16",
                                 moment_dtype="bfloat16",
                                 accum_dtype="bfloat16")),
    ]


def dsv3_variants():
    cfg = get_arch("deepseek-v3-671b")
    shape = SHAPES["train_4k"]
    cfg_a2a = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, impl="ep_a2a"))

    def base_run(mesh_shape, **kw):
        mc = MeshConfig(mesh_shape, ("data", "model"))
        return preset_run(cfg, shape, mc).with_(**kw)

    return None, shape, [
        ("baseline", (16, 16), base_run((16, 16)), cfg),
        # H1: a2a EP (experts over model x data, 1 expert/device) removes
        # the per-microbatch FSDP weight all-gathers (~3.7 TB/step wire);
        # token a2a costs 2*T*k*d instead
        ("ep_a2a", (16, 16), base_run((16, 16), fsdp_experts=False),
         cfg_a2a),
        # H2: + remat dots (drop recompute psums; stash fits: +~7 GiB)
        ("ep_a2a_dots", (16, 16),
         base_run((16, 16), fsdp_experts=False, remat="dots"), cfg_a2a),
        # H3: + microbatches 16->8: expert/attn weights re-read half as
        # often (memory term), a2a volume unchanged
        ("ep_a2a_dots_mb8", (16, 16),
         base_run((16, 16), fsdp_experts=False, remat="dots",
                  microbatches=8), cfg_a2a),
        # H4: TP 16->8 on top of a2a: attention TP psums halve; the a2a
        # exchange (over 'data') is unchanged; experts stay 1/device
        # (8 model x 32 data)
        ("ep_a2a_tp8", (32, 8),
         dataclasses.replace(base_run((32, 8)), fsdp_experts=False),
         cfg_a2a),
    ]


def whisper_variants():
    cfg = get_arch("whisper-small")
    shape = SHAPES["train_4k"]

    def base_run(mesh_shape):
        mc = MeshConfig(mesh_shape, ("data", "model"))
        return preset_run(cfg, shape, mc)

    return cfg, shape, [
        ("baseline", (16, 16), base_run((16, 16))),
        # H1: a 244M-param model has no business on TP=16 — 12 heads can't
        # shard, every projection all-gathers. Crispy-style config choice:
        # pure DP-256 (the 'right cluster shape for the job')
        ("dp256", (256, 1), base_run((256, 1))),
        # H2: middle ground TP=2 (heads 12 % 2 == 0): check whether any TP
        # helps at this scale
        ("dp128_tp2", (128, 2), base_run((128, 2))),
    ]


CELLS = {
    "ds7b": ds7b_variants,
    "dsv3": dsv3_variants,
    "whisper": whisper_variants,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(CELLS), default=None)
    ap.add_argument("--variant", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/perf")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    # every cell compiles for 256 chips on fake host devices; set before
    # the first jax call initializes the backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.makedirs(args.out, exist_ok=True)

    cells = sorted(CELLS) if args.all else [args.cell]
    for cname in cells:
        spec = CELLS[cname]()
        base_cfg, shape, variants = spec[0], spec[1], spec[2]
        for v in variants:
            if len(v) == 4:
                name, mshape, run, cfg = v
            else:
                name, mshape, run = v
                cfg = base_cfg
            if args.variant and name != args.variant:
                continue
            path = os.path.join(args.out, f"{cname}__{name}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {cname}/{name}")
                continue
            try:
                mesh = mesh_of(mshape)
                rec = run_cell(cfg, shape, mesh, run)
                rec["variant"] = name
                rec["mesh_shape"] = list(mshape)
                rec["run_config"] = dataclasses.asdict(run)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"[ok] {cname}/{name}: mesh={mshape} "
                      f"comp={r['compute_s']:.3f} mem={r['memory_s']:.3f} "
                      f"coll={r['collective_s']:.3f} dom={r['dominant']} "
                      f"MFU={r['mfu_bound']:.3f} "
                      f"gib={rec['memory']['per_device_gib']}", flush=True)
            except Exception as e:
                print(f"[FAIL] {cname}/{name}: {type(e).__name__}: {e}",
                      flush=True)
                traceback.print_exc()


if __name__ == "__main__":
    main()
