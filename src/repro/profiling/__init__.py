"""Profiling orchestration: budgeted, adaptive, concurrent, multi-process.

Crispy's whole value proposition is cheap profiling — under ten minutes
per job on a laptop — yet the PR-1 pipeline spends a fixed 5-point ladder
serially on every new signature and keeps its caches per-process. This
package turns profiling itself into a managed resource:

  budget.py     `ProfilingBudget` — the paper's ten-minute envelope as an
                enforced, thread-safe limit (wall clock, accounted profile
                seconds, and point count) shared by everything below.

  executor.py   `ProfilingExecutor` — thread pool the pipeline fans
                fixed-ladder points and the service fans independent
                signature groups over (`map_tasks`); budget gating lives
                in the pipeline's acquisition stage, not here.

  store.py      `ProfileStore` (profile points + calibrated anchors in a
                backend append-only log; `calibrated_anchor` persists
                per-signature anchors so repeat signatures skip
                `calibrate_anchor` entirely), `BackendModelRegistry`
                (read-merge-CAS registry flushes: concurrent services
                lose no records) and the back-compat `LockedModelRegistry`
                file constructor. All sharing is delegated to the
                `repro.state` StateBackend protocol (memory / fcntl file
                / crispy-daemon); no fcntl lives here anymore.

The acquisition loop itself now lives in `repro.pipeline` (PointSource +
drive_placement): both `AllocationService` and `CrispyAllocator` reach
these resources through the unified pipeline's `budget=`, `store=` and
`executor=` knobs; `benchmarks/profiling_adaptive.py` measures fixed-vs-
adaptive points, wall time and requirement error, and
`benchmarks/point_placement.py` compares placement strategies.
"""
from repro.profiling.budget import BudgetExhausted, ProfilingBudget
from repro.profiling.executor import DEFAULT_WORKERS, ProfilingExecutor
from repro.profiling.store import (BackendModelRegistry, FileLock,
                                   HAS_FCNTL, LockedModelRegistry,
                                   ProfileStore, calibrated_anchor)

__all__ = [
    "BackendModelRegistry", "BudgetExhausted", "DEFAULT_WORKERS",
    "FileLock", "HAS_FCNTL", "LockedModelRegistry", "ProfileStore",
    "ProfilingBudget", "ProfilingExecutor", "calibrated_anchor",
]
