"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape) cell against the production mesh — single-pod
(16,16) data x model and multi-pod (2,16,16) pod x data x model — with no
real allocation (ShapeDtypeStruct inputs), then record:

  * compiled.memory_analysis()  — proves the per-device working set,
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline,
  * collective wire bytes parsed from the optimized HLO.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, SHAPES, get_arch, shape_applicable,
                           cell_id)
from repro.configs.base import RunConfig
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.launch.presets import preset_run
from repro.launch.hlo_costs import analyze as hlo_analyze
from repro.launch.roofline import model_flops, roofline_from_hlo
from repro.models.model import Model, input_specs
from repro.optim import AdamWConfig, init_adamw
from repro.sharding.rules import (batch_spec, cache_specs, named,
                                  opt_state_specs, param_specs)
from repro.train.step import TrainState, make_train_step

GiB = 1024 ** 3


def _abstract(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        tree)


def build_lowered(cfg, shape, mesh, run: RunConfig = None):
    """Construct the step function + abstract inputs + shardings for a cell
    and return the jax .lower() result."""
    mcfg = mesh_config(mesh)
    run = run or preset_run(cfg, shape, mcfg)
    model = Model(cfg, run)
    batch, caches = input_specs(cfg, shape, run)
    p_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_specs = param_specs(p_abs, mesh, run)
    dp = 1
    for ax in ("pod", "data"):
        try:
            dp *= mesh.shape[ax]
        except KeyError:
            pass

    def bshard(s):
        # batch dim shards over (pod, data) only when divisible
        # (long_500k has global_batch=1: replicate)
        if s.shape and s.shape[0] % dp == 0:
            return named(mesh, batch_spec(mesh, len(s.shape)))
        from jax.sharding import PartitionSpec as P
        return named(mesh, P())

    bspec = jax.tree.map(bshard, batch)

    if shape.mode == "train":
        acfg = AdamWConfig(moment_dtype=run.moment_dtype,
                           keep_master=(run.param_dtype != "float32"))
        opt_abs = jax.eval_shape(lambda p: init_adamw(p, acfg), p_abs)
        o_specs = opt_state_specs(opt_abs, p_specs, p_abs, mesh, run)
        state_abs = TrainState(p_abs, opt_abs, None)
        state_shard = TrainState(
            jax.tree.map(lambda s: named(mesh, s), p_specs),
            jax.tree.map(lambda s: named(mesh, s), o_specs),
            None)
        step = make_train_step(model, acfg, mesh)
        fn = jax.jit(step, in_shardings=(state_shard, bspec),
                     donate_argnums=(0,) if run.donate else ())
        return fn.lower(state_abs, batch), model

    p_shard = jax.tree.map(lambda s: named(mesh, s), p_specs)
    if shape.mode == "prefill":
        def prefill_fn(params, b):
            return model.prefill(params, b, shape.seq_len, mesh)

        # constrain the returned caches (otherwise XLA replicates the
        # zero-init caches of the ssm/hybrid/vlm fallback path: measured
        # 191 GiB/dev on zamba2 = its full 195 GB cache, per device)
        out_abs = jax.eval_shape(prefill_fn, p_abs, batch)
        c_specs = cache_specs(out_abs[1], mesh, run, shape.global_batch)
        out_shard = (None, jax.tree.map(lambda s: named(mesh, s), c_specs))
        fn = jax.jit(prefill_fn, in_shardings=(p_shard, bspec),
                     out_shardings=out_shard)
        return fn.lower(p_abs, batch), model

    # decode
    c_specs = cache_specs(caches, mesh, run, shape.global_batch)
    c_shard = jax.tree.map(lambda s: named(mesh, s), c_specs)

    def decode_fn(params, b, c):
        return model.decode_step(params, b, c, mesh)

    fn = jax.jit(decode_fn, in_shardings=(p_shard, bspec, c_shard),
                 donate_argnums=(2,) if run.donate else ())
    return fn.lower(p_abs, batch, caches), model


def run_cell(cfg, shape, mesh, run: RunConfig = None, hlo_out: str = None):
    t0 = time.monotonic()
    lowered, model = build_lowered(cfg, shape, mesh, run)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0
    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)
    n_dev = mesh.devices.size
    hc = hlo_analyze(hlo, n_dev)
    mf = model_flops(cfg, shape)
    # analytic non-dot HBM traffic: optimizer elementwise update reads and
    # writes params + m + v (+ master) once per step
    extra = 0.0
    if shape.mode == "train":
        extra = 2.0 * float(ma.argument_size_in_bytes)
    roof = roofline_from_hlo(hc, n_dev, mf, extra_hbm_bytes=extra)
    per_dev_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
                     ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    rec = {
        "cell": cell_id(cfg.name, shape.name),
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": list(mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "n_devices": int(n_dev),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
            "per_device_bytes": int(per_dev_bytes),
            "per_device_gib": round(per_dev_bytes / GiB, 3),
        },
        "cost_analysis_raw": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "hlo_costs": {
            "dot_flops_per_dev": hc.dot_flops,
            "dot_bytes_per_dev": hc.dot_bytes,
            "n_while": hc.n_while,
            "max_trip_multiplier": hc.max_mult,
        },
        "collectives": {
            "wire_bytes_per_dev": hc.coll_wire_bytes,
            "by_kind": hc.coll_by_kind,
            "counts": hc.coll_counts,
        },
        "roofline": {
            "compute_s": roof.compute_s,
            "memory_s": roof.memory_s,
            "collective_s": roof.collective_s,
            "dominant": roof.dominant,
            "model_flops_total": mf,
            "model_flops_per_dev": roof.model_flops,
            "hlo_flops_per_dev": roof.flops_per_dev,
            "useful_flops_fraction": roof.useful_flops_fraction,
            "mfu_bound": roof.mfu_bound,
        },
        "params": {
            "total": cfg.param_count(),
            "active": cfg.active_param_count(),
        },
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    # the production meshes need 512 fake host devices; set before the
    # first jax call initializes the backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    os.makedirs(args.out, exist_ok=True)
    suffix = "multipod" if args.multi_pod else "singlepod"

    cells = []
    if args.all:
        for cfg in ARCHS.values():
            for shape in SHAPES.values():
                cells.append((cfg, shape))
    else:
        cells.append((get_arch(args.arch), SHAPES[args.shape]))

    failures = 0
    for cfg, shape in cells:
        name = f"{cfg.name}__{shape.name}__{suffix}"
        path = os.path.join(args.out, name + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {name}")
            continue
        if not shape_applicable(cfg, shape):
            rec = {"cell": cell_id(cfg.name, shape.name), "skipped": True,
                   "reason": "long_500k requires sub-quadratic attention "
                             "(DESIGN.md §4)"}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[SKIP-BY-DESIGN] {name}")
            continue
        try:
            rec = run_cell(cfg, shape, mesh)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            m = rec["memory"]["per_device_gib"]
            r = rec["roofline"]
            print(f"[ok] {name}: {m} GiB/dev, dominant={r['dominant']}, "
                  f"mfu_bound={r['mfu_bound']:.3f}, "
                  f"compile={rec['compile_s']}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
            with open(path + ".err", "w") as f:
                f.write(traceback.format_exc())
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
