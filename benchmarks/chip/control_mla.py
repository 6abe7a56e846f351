"""Readings that set the correctness limit of a cell that
`drivers/decode_mla_moe.py` drives, on the chip at the cell's own size, in
one process over many seeds.

  python benchmarks/chip/control_mla.py --workload <name> --seconds <s> \\
      --seeds <n> [<n> ...] [--control-seeds <k>] [--fault-seeds <k>] \\
      [--faults <f> ...] [--dump <dir>]

For each seed it prints one JSON line: `served_gap` for the program and
whether the run came out correct, the run's end-to-end metrics, the
largest gap against each reference alone, how near the routing came to
a tie at the token of the widest gap, and the checked tokens whose
expert set at some MoE layer differs between the float32 reference and
the one at the program's bfloat16 (`other_sets`, of `checked`). For the
first `--control-seeds` seeds it adds the control, the plain reference
one precision lower (fp8) put in the program's place and scored as the
program is; for the first `--fault-seeds`, each fault of `--faults`
planted in the program (`FAULTS`), a run of the cell's own size each.
The control and every fault should come out not correct. With `--dump`,
each seed's per-token gaps against each reference, for the program, the
control and the faults, and the routing margins go to `<dir>/<seed>.npz`.
`control.py` does the same for the cells of the other drivers; the
benchmark's own runs never run either.
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

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from chipbench.harness import (Bench, Cell, Check, load_module,  # noqa: E402
                               read_json)

# faults of the model step: the router scored by a softmax over the
# experts in place of each expert's sigmoid; the MLA softmax without
# YaRN's mscale^2
FAULTS = ("softmax_router", "no_mscale")


@contextlib.contextmanager
def planted(fault: str):
    from repro.models import attention, moe
    if fault == "softmax_router":
        name, mod, orig = "route", moe, moe.route

        def patched(w, x, m, bias=None):
            return orig(w, x, dataclasses.replace(m, scoring="softmax"), bias)
    else:
        name, mod, orig = "mla_softmax_scale", attention, \
            attention.mla_softmax_scale

        def patched(cfg):
            return (cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim) ** -0.5
    setattr(mod, name, patched)
    try:
        yield
    finally:
        setattr(mod, name, orig)


def widest_tie(gaps, ties, valid) -> float:
    """How near the routing came to a tie (`refmla.route`), at the least
    over the references and the MoE layers, at the token whose served gap
    is the widest."""
    i = np.argmax(np.where(valid, gaps.min(0), -np.inf))
    return float(ties.min(axis=(0, 1)).reshape(-1)[i])


def verdict(checks) -> dict:
    return {"checks": {c.name: c.value for c in checks},
            "correct": all(c.ok for c in checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS)
    ap.add_argument("--dump", type=Path)
    args = ap.parse_args(argv)
    cell = Cell.find(read_json(ROOT / "BENCHMARK.json"), args.workload)
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py",
                         "chipbench_driver")
    limit = cell.limits["served_gap"]
    as_run = cell.config["as_run"]
    if args.dump:
        args.dump.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(args.seeds):
        bench = Bench.on_chip(cell, seed, args.seconds, False,
                              time.monotonic())
        out = driver.run(bench)
        k = out.kept
        sound = driver.gaps(k["reference"], k["served"])
        got = {"seed": seed, **verdict(out.checks),
               "memory_peak_bytes": bench.memory_peak,
               "end_to_end": out.end_to_end,
               "by_reference": dict(zip(driver.SOUND, (
                   float(g[k["valid"]].max()) for g in sound))),
               "other_sets": driver.other_expert_sets(k["chosen"],
                                                      k["valid"]),
               "widest_tie": widest_tie(sound, k["ties"], k["valid"]),
               "checked": int(k["valid"].sum())}
        dump = {"sound": sound, "valid": k["valid"], "ties": k["ties"]}
        if i < args.control_seeds:
            fp8 = driver.reference(bench, as_run, k["sample"], ("fp8",))[0]
            tokens = fp8[0].argmax(-1)
            gap = driver.served_gap(k["reference"], tokens, k["valid"])
            got["control"] = verdict([Check("served_gap", float(gap.max()),
                                            limit)])
            dump["control"] = driver.gaps(k["reference"], tokens)
        if i < args.fault_seeds:
            got["faults"] = {}
            for fault in args.faults:
                fb = Bench.on_chip(cell, seed, args.seconds, False,
                                   time.monotonic())
                with planted(fault):
                    fo = driver.run(fb)
                got["faults"][fault] = verdict(fo.checks)
                dump[fault] = driver.gaps(fo.kept["reference"],
                                          fo.kept["served"])
                dump[f"{fault}_valid"] = fo.kept["valid"]
                del fo
                gc.collect()
        if args.dump:
            np.savez(args.dump / f"{seed}.npz", **dump)
        print(json.dumps(got), flush=True)
        del out, k
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
