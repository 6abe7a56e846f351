"""The chip benchmark: one run of one cell of BENCHMARK.json.

  python benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

Run from a checkout, on a machine that holds the cell's chips. Set-up
(weights and inputs from the seed, compiles, warm-up) ends where the
measured window opens; the window lasts `--seconds`. After it closes the
run reads the peak device memory, frees the program's state and compares
what the window produced with a plain reference. The last line of
standard output is the result as JSON: with `--trace 0` the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics and the device
trace's busy time and breakdown. The numbers compared, each beside its
limit, are the last lines of standard error. Without a TPU, or with fewer
chips than the cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import os  # noqa: E402

# libtpu would otherwise keep its logs under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from chipbench import tracing  # noqa: E402
from chipbench.harness import (Bench, BenchError, Cell, load_module,  # noqa: E402
                               log, metric_reader, read_json)


def layer_metrics(bench: Bench, outcome) -> dict:
    out = {}
    for m in bench.cell.per_layer():
        reader = load_module(metric_reader(m["name"]),
                             f"chipbench_metric_{len(out)}")
        value = reader.read(bench, outcome)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_fields(bench: Bench) -> tuple:
    tr = bench.load_trace()
    lo, hi = tracing.window_bounds(tr)
    planes = tracing.device_planes(tr)
    busy = tracing.busy_s(tr, lo, hi, planes)
    breakdown = {
        "device_ops": tracing.top(tracing.op_seconds(tr, lo, hi, planes)),
        "idle_gaps": [list(g) for g in tracing.idle_gaps(tr, lo, hi)[:10]]}
    return {"busy_s": busy, "window_s": (hi - lo) / 1e9}, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = read_json(ROOT / "BENCHMARK.json")
    cell = Cell.find(spec, args.workload)
    bench = Bench.on_chip(cell, args.seed, args.seconds, bool(args.trace),
                          T0)
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py",
                         "chipbench_driver")
    outcome = driver.run(bench)
    if bench.memory_peak is None:
        raise BenchError("the driver did not read the peak memory")

    dev = bench.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(bench.devices),
              "memory_peak_bytes": bench.memory_peak}
    result = {"correct": all(c.ok for c in outcome.checks) and
              bool(outcome.checks),
              "attempted": outcome.attempted, "failed": outcome.failed}
    if args.trace:
        result["metrics"] = layer_metrics(bench, outcome)
        busy, breakdown = trace_fields(bench)
        device.update(busy)
        result["device"] = device
        result["breakdown"] = breakdown
    else:
        metrics = {"setup_s": bench.setup_s, **outcome.end_to_end}
        result["metrics"] = {m["name"]: {"value": float(metrics[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end()}
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    for note in outcome.notes:
        log(note)
    log(f"window: {bench.window_s:.3f}s, set-up {bench.setup_s:.3f}s, "
        f"compiles inside it {bench.watch.compiles} "
        f"({bench.watch.compile_s:.3f}s), cache hits inside it "
        f"{bench.watch.cache_hits}")
    print(json.dumps(result), flush=True)
    for c in outcome.checks:
        log(f"check {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
