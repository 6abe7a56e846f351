"""The HBM profile source: the paper's profiling runs for TPU jobs.

Paper step                      ->  here
1. five small dataset samples   ->  reduced-DEPTH variants of the job
                                    (`ladder`: same family, same shape —
                                    depth is the knob per-device memory
                                    is linear in: layer params +
                                    optimizer state + activation stash)
2. profile on a single machine  ->  AOT-compile each variant for the
                                    devices of the given mesh (the chip,
                                    on a TPU host) and read
                                    compiled.memory_analysis()

The fit, the extrapolation and the choice of slice are the pipeline's:
`AllocationPipeline` (repro/pipeline) takes `profile_at(...)` as its
profile callable, with the TPU catalog and `TPU_OVERHEAD_GIB`. The bytes
it reports are aggregate — per-device bytes x the mesh's devices — the
analogue of the paper's total-cluster-memory requirement that the
catalog's usable memory is compared against.

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
from typing import Callable, List, Optional

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.profiler import ProfileResult
from repro.core.sampling import integer_ladder
from repro.telemetry import default_registry, span_if

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


class HBMPlanner:
    """Turns a depth of a job into the bytes its compiled step holds."""

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
        """The distinct depths `_reduced_depth` compiles for the job's
        ladder, shallowest first: the sizes the pipeline fits against."""
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
        return sorted({_reduced_depth(cfg, L).n_layers
                       for L in integer_ladder(anchor, n=5, lo=lo)})

    def profile_at(self, cfg: ModelConfig, shape: ShapeConfig, mesh,
                   run: Optional[RunConfig] = None
                   ) -> Callable[[float], ProfileResult]:
        """The pipeline's profile callable: a size is a depth, compiled as
        `_reduced_depth(cfg, round(size))` on `mesh`, and reported as the
        aggregate bytes (per-device bytes x the mesh's devices)."""
        n_dev = mesh.devices.size

        def at(size: float) -> ProfileResult:
            t0 = time.monotonic()
            small = _reduced_depth(cfg, int(round(size)))
            per_dev = self.profile_memory(small, shape, mesh, run)
            return ProfileResult(size, per_dev * n_dev, 0.0,
                                 time.monotonic() - t0)
        return at
