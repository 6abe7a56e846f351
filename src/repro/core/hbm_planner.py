"""Crispy for TPU slices: the paper's pipeline applied to mesh selection.

Paper step                      ->  here
1. five small dataset samples   ->  five reduced-DEPTH variants of the job
                                    (n_layers ladder; same family, same
                                    shape — depth is the knob per-device
                                    memory is linear in: layer params +
                                    optimizer state + activation stash)
2. profile on a single machine  ->  AOT-compile each variant on this CPU
                                    host against a small profile mesh and
                                    read compiled.memory_analysis()
3. OLS + R^2 > .99 gate         ->  identical (core/memory_model.py)
4. pick cheapest feasible config->  BFA over the TPU catalog restricted to
                                    configs with enough aggregate HBM

The extrapolation target is aggregate HBM = per-device bytes x devices,
the analogue of the paper's total-cluster-memory requirement; per-chip
feasibility is additionally checked on the (divided) per-device estimate.
Validation against ground-truth full compiles: EXPERIMENTS.md §Planner.

Telemetry (while the process registry is enabled): each
`profile_memory` is a span `planner.profile` (attributes `n_layers`,
`seq_len`, `batch`) with the children `planner.lower` (eval_shape, the
trace and the lowering), `planner.compile` (XLA's compile, whose
`compile_s` the span system adds from jax.monitoring) and
`planner.memory` (memory_analysis). Under a decision they nest inside
`pipeline.acquire`.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax

from repro.configs.base import MeshConfig, ModelConfig, RunConfig, ShapeConfig
from repro.core.catalog import ClusterConfig, NodeType, tpu_catalog
from repro.core.history import ExecutionHistory
from repro.core.memory_model import LinearMemoryModel, fit_memory_model
from repro.core.sampling import integer_ladder
from repro.core.selector import Selection, select_bfa
from repro.telemetry import default_registry, span_if

GiB = 1024 ** 3
TPU_OVERHEAD_GIB = 1.25       # XLA runtime / infeed / collective scratch


def _reduced_depth(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """Same architecture, fewer layers (hybrid/vlm keep group structure)."""
    if cfg.hybrid is not None:
        period = cfg.hybrid.period
        n_layers = max(period, (n_layers // period) * period)
    if cfg.cross_attn is not None:
        period = cfg.cross_attn.period
        n_layers = max(period, (n_layers // period) * period)
    return dataclasses.replace(cfg, n_layers=n_layers)


def compiled_bytes(compiled) -> float:
    """Per-device bytes a compiled step holds: live arguments + outputs +
    temporaries, less the outputs that alias donated arguments."""
    ma = compiled.memory_analysis()
    return float(ma.argument_size_in_bytes + ma.output_size_in_bytes +
                 ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@dataclass
class PlanReport:
    job: str
    ladder: List[int]
    per_dev_bytes: List[float]
    model: LinearMemoryModel
    predicted_per_dev_gib: float      # at full depth, on the profile mesh
    requirement_gib: float            # aggregate, extrapolated
    selection: Optional[Selection]
    profile_wall_s: float
    profile_mesh_devices: int


class HBMPlanner:
    def __init__(self, catalog: Optional[List[ClusterConfig]] = None,
                 history: Optional[ExecutionHistory] = None,
                 overhead_gib: float = TPU_OVERHEAD_GIB,
                 leeway: float = 0.05):
        self.catalog = catalog if catalog is not None else tpu_catalog()
        self.history = history
        self.overhead = overhead_gib
        self.leeway = leeway

    # -- profiling ----------------------------------------------------------
    def profile_memory(self, cfg: ModelConfig, shape: ShapeConfig, mesh,
                       run: Optional[RunConfig] = None) -> float:
        """Per-device bytes of the job's step on `mesh` via AOT compile."""
        from repro.launch.dryrun import build_lowered
        on = default_registry().enabled
        with span_if(on, "planner.profile", n_layers=cfg.n_layers,
                     seq_len=shape.seq_len, batch=shape.global_batch):
            with span_if(on, "planner.lower"):
                lowered, _ = build_lowered(cfg, shape, mesh, run)
            with span_if(on, "planner.compile"):
                compiled = lowered.compile()
            with span_if(on, "planner.memory"):
                return compiled_bytes(compiled)

    @staticmethod
    def ladder(cfg: ModelConfig,
               anchor_layers: Optional[int] = None) -> List[int]:
        """The depths `plan` profiles, shallowest first."""
        anchor = anchor_layers or max(2, min(cfg.n_layers // 4, 12))
        # lo >= 2: a length-1 scan is inlined by XLA and its buffer liveness
        # differs from the scanned steady state — the analogue of the
        # paper's "sample large enough that startup doesn't dominate"
        lo = 2
        if cfg.hybrid is not None:
            lo = cfg.hybrid.period
            anchor = max(anchor, 3 * lo)
        if cfg.cross_attn is not None:
            lo = cfg.cross_attn.period
            anchor = max(anchor, 3 * lo)
        return integer_ladder(anchor, n=5, lo=lo)

    def plan(self, cfg: ModelConfig, shape: ShapeConfig, profile_mesh,
             run: Optional[RunConfig] = None,
             anchor_layers: Optional[int] = None,
             select: bool = True) -> PlanReport:
        t0 = time.monotonic()
        n_dev = profile_mesh.devices.size
        ladder = self.ladder(cfg, anchor_layers)
        mems = []
        for L in ladder:
            small = _reduced_depth(cfg, L)
            mems.append(self.profile_memory(small, shape, profile_mesh, run))
        # fit vs the *effective* layer counts after family rounding
        eff = [_reduced_depth(cfg, L).n_layers for L in ladder]
        model = fit_memory_model(eff, mems)
        pred_dev = model.requirement(cfg.n_layers, self.leeway)
        req_gib = pred_dev * n_dev / GiB
        wall = time.monotonic() - t0
        sel = None
        if select:
            sel = self.select(req_gib, pred_dev / GiB if model.confident
                              else 0.0, job=f"{cfg.name}:{shape.name}")
        return PlanReport(f"{cfg.name}:{shape.name}", list(eff), mems, model,
                          pred_dev / GiB, req_gib, sel, wall, n_dev)

    # -- selection ------------------------------------------------------------
    def select(self, requirement_gib: float, per_dev_gib_at_profile: float,
               job: str = "") -> Selection:
        feasible = []
        for c in self.catalog:
            usable = c.usable_mem_gib(self.overhead)
            if usable < requirement_gib:
                continue
            # per-chip check: aggregate requirement divided over this slice
            if requirement_gib > 0 and \
                    requirement_gib / c.scale_out > c.node.mem_gib - self.overhead:
                continue
            feasible.append(c)
        fell_back = requirement_gib <= 0.0
        if not feasible:
            feasible = sorted(
                self.catalog,
                key=lambda c: -c.usable_mem_gib(self.overhead))[:1]
            fell_back = True
        if self.history is not None:
            cfg = select_bfa(feasible, self.history, exclude_job=job)
        else:
            cfg = min(feasible, key=lambda c: c.usd_per_hour)
        return Selection(cfg, "crispy-hbm", requirement_gib, len(feasible),
                         fell_back)
