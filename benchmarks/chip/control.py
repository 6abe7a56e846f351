"""Readings that set a cell's correctness limits, on the chip at the
cell's own size, in one process over many seeds.

  python benchmarks/chip/control.py --workload <name> --seconds <s> \\
      --seeds <n> [<n> ...] [--control-seeds <k>] [--fault-seconds <s>]

For each seed it prints one JSON line: the numbers the cell compares for
the program (the lower readings) and whether the run came out correct.
For the first `--control-seeds` seeds it adds the control, the plain
reference put in the program's place one precision lower (fp8 for the
bfloat16 model, float32 for the planner's float64 fit), compared with the
cell's own limits as a run compares the program; and, for the planner,
each fault of `FAULTS` planted in the program's profile, a run of
`--fault-seconds` each. The control and every fault should come out not
correct. The benchmark's own runs never run this.
"""
from __future__ import annotations

import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import refplanner  # noqa: E402
from chipbench.harness import (Bench, Cell, Check, load_module,  # noqa: E402
                               read_json)

# faults of the profile layer, planted in `HBMPlanner.profile_memory`:
# each ladder point compiled one layer short; every point compiled at
# depth 2, the ladder's shallowest; every job's cache compiled at the
# warm-up's length
FAULTS = ("depth_short", "depth_fixed", "shape_fixed")


@contextlib.contextmanager
def planted(fault: str, traffic: dict):
    from repro.core.hbm_planner import HBMPlanner
    orig = HBMPlanner.profile_memory

    def profile_memory(self, cfg, shape, mesh, run=None):
        if fault == "depth_short":
            cfg = dataclasses.replace(cfg, n_layers=cfg.n_layers - 1)
        elif fault == "depth_fixed":
            cfg = dataclasses.replace(cfg, n_layers=2)
        else:
            shape = dataclasses.replace(shape, seq_len=traffic["warmup"][0])
        return orig(self, cfg, shape, mesh, run)

    HBMPlanner.profile_memory = profile_memory
    try:
        yield
    finally:
        HBMPlanner.profile_memory = orig


def verdict(checks) -> dict:
    return {"checks": {c.name: c.value for c in checks},
            "correct": all(c.ok for c in checks)}


def decode(driver, bench, args) -> dict:
    out = driver.run(bench)
    got = verdict(out.checks)
    if args.control:
        k = out.kept
        gap, _ = driver.served_gaps(bench, bench.cell.config["as_run"],
                                    k["sample"], "fp8", against=k["reference"])
        got["control"] = verdict([Check("served_gap", float(gap.max()),
                                        bench.cell.limits["served_gap"])])
    return got


def decide(driver, bench, args) -> dict:
    out = driver.run(bench)
    got = verdict(out.checks)
    if args.control:
        k = out.kept
        full = bench.cell.config["as_run"]["n_layers"]
        gap = max(refplanner.gap(
            refplanner.decide(pts, k["catalog"], k["overhead"], full,
                              np.float32),
            refplanner.decide(pts, k["catalog"], k["overhead"], full))
            for pts, _ in k["answers"])
        got["control"] = verdict([Check("decide_gap", gap,
                                        bench.cell.limits["decide_gap"])])
        got["faults"] = {}
        for fault in FAULTS:
            fb = Bench.on_chip(bench.cell, bench.seed, args.fault_seconds,
                               False, time.monotonic())
            with planted(fault, bench.cell.traffic):
                got["faults"][fault] = verdict(driver.run(fb).checks)
            gc.collect()
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = Cell.find(read_json(ROOT / "BENCHMARK.json"), args.workload)
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py",
                         "chipbench_driver")
    kind = {"decode": decode, "decide": decide}[cell.traffic["driver"]]
    for i, seed in enumerate(args.seeds):
        bench = Bench.on_chip(cell, seed, args.seconds, False,
                              time.monotonic())
        args.control = i < args.control_seeds
        got = kind(driver, bench, args)
        print(json.dumps({"seed": seed, **got}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
