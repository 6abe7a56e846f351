"""Allocation window: one client sends fresh jobs back to back through
`AllocationEndpoint.handle`, as chip_smoke's allocate phase does.

Each request is the configuration's model at a (seq_len, batch) of the
traffic file's grid, drawn from the seed without repeats, so no two
decisions of a run share a ladder point: the service's own defaults (no
history, fixed ladder, cheapest fit) profile every point of
`HBMPlanner.ladder` by compiling the step for this chip
(`HBMPlanner.profile_memory`). JAX's persistent cache is off for all of
the service's compiles: a compile the cache serves is not the work a
fresh job pays for. Set-up makes one decision at a shape off the grid, so
that the process's first compiles, slower than the rest, are paid before
the window. A run whose window is served from the cache all the same
says so and counts those decisions as failed.

Once the window has closed, each decision is checked against a plain
reference: a least-squares line through the ladder points it measured,
extrapolated to the full depth, and the cheapest slice of the catalog the
service was given that holds it. The points themselves are checked
against floors counted from the configuration file: at each depth the
step holds at least its parameters as stored and one copy of its cache
at the job's (seq_len, batch), each layer adds at least its share, and
the bytes grow with the cache (`refplanner.ladder_floors`).
"""
from __future__ import annotations

import dataclasses
import time

from chipbench import refplanner, tracing
from chipbench.harness import Check, Outcome, model_config


def grid(traffic, rng):
    lo, hi, step = traffic["seq_len"]
    b_lo, b_hi = traffic["batch"]
    cells = [(s, b) for s in range(lo, hi + 1, step)
             for b in range(b_lo, b_hi + 1)]
    return [cells[i] for i in rng.permutation(len(cells))]


def run(bench):
    from jax.experimental.compilation_cache import compilation_cache as cc
    import jax
    from repro.allocator.service import AllocationService
    from repro.configs import SHAPES
    from repro.core.catalog import tpu_catalog
    from repro.core.hbm_planner import (HBMPlanner, TPU_OVERHEAD_GIB,
                                        _reduced_depth)
    from repro.core.history import ExecutionHistory
    from repro.core.profiler import ProfileResult
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import AllocationEndpoint

    tr = bench.cell.traffic
    cfg = model_config(bench.cell.config)
    base = SHAPES[tr["shape"]]
    mesh = make_mesh((1, 1), ("data", "model"), devices=bench.devices[:1])
    planner = HBMPlanner()
    ladder = planner.ladder(cfg)
    catalog = tpu_catalog()
    points = {}                       # job -> [(depth, bytes)]
    shapes = {}                       # job -> (seq_len, batch)

    def job_for(seq, batch):
        shape = dataclasses.replace(base, seq_len=seq, global_batch=batch)
        job = f"{cfg.name}:{shape.name}:seq{seq}xb{batch}"
        points[job], shapes[job] = [], (seq, batch)

        def profile_at(size: float) -> ProfileResult:
            t0 = time.monotonic()
            depth = int(round(size))
            with tracing.annotate("bench.profile_point"):
                small = _reduced_depth(cfg, depth)
                per_dev = planner.profile_memory(small, shape, mesh)
            wall = time.monotonic() - t0
            points[job].append((depth, per_dev))
            return ProfileResult(size, per_dev, 0.0, wall)

        return dict(job=job, profile_at=profile_at, full_size=cfg.n_layers,
                    anchor=ladder[-1], sizes=ladder)

    walls, answers = [], []
    # the service's compiles are the profile layer's own work: none may
    # come from the persistent cache, in the window or in the warm-up that
    # pays the process's first compiles
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with AllocationService(catalog, ExecutionHistory(),
                           overhead_per_node_gib=TPU_OVERHEAD_GIB) as svc:
        ep = AllocationEndpoint(svc)
        ep.handle(**job_for(*tr["warmup"]))
        tel = svc.telemetry
        hist = tel.histogram("acquisition.profile_seconds")
        fresh = tel.counter("acquisition.fresh")
        h0, f0 = hist.summary(), fresh.value
        todo = grid(tr, bench.rng)
        with bench.window():
            t_stop = bench.t_start + bench.seconds
            t = bench.t_start
            while t < t_stop and todo:
                req = job_for(*todo.pop(0))
                hits = bench.watch.cache_hits
                with tracing.annotate("bench.decision"):
                    wire = ep.handle(include_trace=True, **req)
                t1 = time.monotonic()
                walls.append(t1 - t)
                answers.append((req["job"], wire,
                                bench.watch.cache_hits - hits))
                t = t1
            bench.end_window(t)
        h1, f1 = hist.summary(), fresh.value
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()
    bench.read_memory_peak()

    c = bench.cell.config["as_run"]
    gaps, failed, notes = [], 0, []
    for job, wire, hits in answers:
        if hits:
            notes.append(f"decide: {job} was served {hits} programs from "
                         f"the persistent compile cache; counted failed")
            failed += 1
        ref = refplanner.decide(points[job], catalog_rows(catalog),
                                TPU_OVERHEAD_GIB, c["n_layers"])
        gaps.append(refplanner.gap(wire, ref))
        notes.append(f"decide: {job} points={points[job]} "
                     f"requirement_gib={wire['requirement_gib']!r} "
                     f"config={wire['config']} reference={ref}")
    done = len(answers)
    lims = bench.cell.limits
    floors = refplanner.ladder_floors(
        [(*shapes[j], points[j]) for j, _, _ in answers], c,
        bench.cell.config["stored_bytes"]) if answers else {}
    notes.append(f"decide: floors over measured {floors}")
    stage = [sum(a[1]["trace"]["stage_walls"].get(k, 0.0)
                 for k in ("fit", "extrapolate", "select")) for a in answers]
    return Outcome(
        attempted=done, failed=failed,
        end_to_end={"decision_s": sum(walls) / done if done else float("nan")},
        checks=[Check("decide_gap", max(gaps) if gaps else float("inf"),
                      lims["decide_gap"])] +
        [Check(k, v, lims[k]) for k, v in floors.items() if v is not None],
        layer={"decisions": done, "fresh": f1 - f0,
               "profile_count": h1["count"] - h0["count"],
               "profile_sum": h1["sum"] - h0["sum"],
               "fit_select_s": stage},
        notes=notes + [f"decide: {done} decisions, walls {walls}"],
        kept={"answers": [(points[j], w) for j, w, _ in answers],
              "shapes": [shapes[j] for j, _, _ in answers],
              "catalog": catalog_rows(catalog),
              "overhead": TPU_OVERHEAD_GIB})


def catalog_rows(catalog):
    """The catalog the service was given, as plain rows for the reference:
    (name, chips, HBM GiB per chip, USD per hour)."""
    return [(c.name, c.scale_out, c.node.mem_gib, c.usd_per_hour)
            for c in catalog]
