"""Profiling orchestration subsystem: adaptive ladder placement over the
budgeted point source (early stop / escalation / budget exhaustion), the
shared profiling budget, the file-locked multi-process profile & anchor
store, the locked registry's merge-on-flush, and the
AllocationService/CrispyAllocator/endpoint wiring.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.allocator import (AllocationRequest, AllocationService,
                             ModelRegistry)
from repro.allocator.model_zoo import fit_zoo
from repro.core.catalog import aws_like_catalog
from repro.core.crispy import CrispyAllocator
from repro.core.memory_model import fit_memory_model
from repro.core.profiler import ProfileResult
from repro.core.sampling import integer_ladder, ladder_from_anchor
from repro.core.simulator import (GiB, build_history, make_profile_fn,
                                  scout_like_jobs)
from repro.pipeline import LadderPlacer, PointSource, drive_placement
from repro.profiling import (FileLock, LockedModelRegistry, ProfileStore,
                             ProfilingBudget, ProfilingExecutor,
                             calibrated_anchor)
from repro.serve.engine import AllocationEndpoint

FULL = 1e11
LADDER = ladder_from_anchor(FULL * 0.01).sizes


# -- budget -------------------------------------------------------------------


def test_budget_limits_and_refund():
    b = ProfilingBudget(max_points=2, charge_s=100.0)
    assert b.try_spend() and b.try_spend()
    assert not b.try_spend()                 # point limit
    assert b.denials == 1
    b.refund()
    assert b.try_spend()                     # refund reopened a slot
    b.charge(250.0)
    b2 = ProfilingBudget(charge_s=100.0)
    b2.charge(250.0)
    assert not b2.try_spend() and b2.exhausted()
    snap = b.snapshot()
    assert snap["points_spent"] == 2 and snap["charged_s"] == 250.0


def test_budget_thread_safety():
    b = ProfilingBudget(max_points=100)
    granted = []

    def worker():
        for _ in range(50):
            if b.try_spend():
                granted.append(1)

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(granted) == 100               # never over-granted


# -- adaptive placement over the budgeted point source ------------------------
# the loop AllocationPipeline.measure_plan runs: LadderPlacer placement over
# PointSource.acquire, which owns the budget gate and charge


def _adaptive(mem_of_size, wall=10.0, calls=None, budget=None,
              fit=fit_zoo):
    def profile_at(s):
        if calls is not None:
            calls.append(s)
        return ProfileResult(s, mem_of_size(s), 0.0, wall)
    source = PointSource("sig", profile_at, budget=budget)
    return drive_placement(LadderPlacer(), LADDER, FULL, source.acquire,
                           fit)


def test_early_stop_on_clean_linear_job():
    """A perfectly linear job must stop at <= 3 of the 5 ladder points and
    still extrapolate exactly."""
    calls = []
    ap = _adaptive(lambda s: 0.9 * s + 1.6e9, calls=calls)
    assert ap.early_stop
    assert len(ap.sizes) <= 3 < len(LADDER)
    assert len(calls) == ap.fresh == len(ap.sizes)
    assert ap.fit.confident
    truth = 0.9 * FULL + 1.6e9
    assert abs(ap.fit.predict(FULL) - truth) / truth < 1e-6
    # smallest-first: the points profiled are the cheapest ladder prefix
    assert ap.sizes == sorted(LADDER)[:len(ap.sizes)]


def test_escalation_on_noisy_job():
    """Noisy data: candidates disagree at full size, the placer spends
    extra points beyond the base ladder, and stays unconfident."""
    rng = np.random.default_rng(3)
    noise = {}

    def mem(s):
        if s not in noise:
            noise[s] = 1 + rng.normal(0, 0.09)
        return s * noise[s]

    ap = _adaptive(mem)
    assert ap.escalated
    assert len(ap.sizes) > len(LADDER)
    assert not ap.early_stop
    assert not ap.fit.confident              # degrades like the paper
    assert ap.fit.requirement(FULL) == 0.0
    # escalation densifies the measured range, never extrapolates past it
    assert max(ap.sizes) <= max(LADDER)


def test_budget_exhaustion_mid_ladder_falls_back_gracefully():
    budget = ProfilingBudget(max_points=2)
    ap = _adaptive(lambda s: 0.9 * s, budget=budget)
    assert ap.budget_exhausted
    assert len(ap.sizes) == 2
    assert not ap.fit.confident              # 2 points never pass LOOCV
    assert ap.fit.requirement(FULL) == 0.0   # -> BFA fallback downstream
    # a budget that denies even the first point still returns a fit object
    ap0 = _adaptive(lambda s: s, budget=ProfilingBudget(max_points=0))
    assert ap0.budget_exhausted and len(ap0.sizes) == 0
    assert not ap0.fit.confident


def test_budget_charges_reported_profile_seconds():
    """Simulated runs report minutes of wall time while taking micro-
    seconds; charging the *reported* seconds reproduces the envelope."""
    rng = np.random.default_rng(11)
    budget = ProfilingBudget(charge_s=25.0)
    ap = _adaptive(lambda s: s * (1 + rng.normal(0, 0.2)), wall=10.0,
                   budget=budget)
    # 10s per run: the third try_spend sees 20s charged < 25s, the fourth
    # sees 30s and is denied (noisy data never early-stops before then)
    assert len(ap.sizes) == 3
    assert ap.budget_exhausted
    assert budget.charged_s == 30.0


def test_scheduler_with_papers_linear_fitter():
    """A custom (non-zoo) fitter drives the same early-stop logic."""
    ap = _adaptive(lambda s: 2.0 * s, fit=fit_memory_model)
    assert ap.early_stop and len(ap.sizes) <= 3
    assert ap.fit.confident


# -- persistent store ---------------------------------------------------------


def test_profile_store_round_trip_and_refresh(tmp_path):
    path = str(tmp_path / "prof.jsonl")
    s1 = ProfileStore(path)
    s1.put("sigA", 1e9, ProfileResult(1e9, 2e9, 0.0, 5.0))
    s1.put_anchor("sigA", 1e9)
    # a second handle (fresh process equivalent) sees everything
    s2 = ProfileStore(path)
    got = s2.get("sigA", 1e9)
    assert got is not None and got.peak_mem_bytes == 2e9
    assert s2.get_anchor("sigA") == 1e9
    # writes by the sibling appear after refresh, not before
    s2.put("sigB", 2e9, ProfileResult(2e9, 4e9, 0.0, 5.0))
    assert s1.get("sigB", 2e9) is None
    assert s1.refresh() >= 1
    assert s1.get("sigB", 2e9) is not None


def test_calibrated_anchor_skips_measurement_on_repeat(tmp_path):
    store = ProfileStore(str(tmp_path / "prof.jsonl"))
    runs = []

    def run_at(size):
        runs.append(size)
        return 1.0                           # lands in the target band

    a1 = calibrated_anchor(store, "sig", run_at, 1e9)
    assert runs                              # first time measures
    n = len(runs)
    a2 = calibrated_anchor(store, "sig", run_at, 1e9)
    assert a2 == a1 and len(runs) == n       # repeat skips entirely


def test_two_processes_share_locked_store_without_corruption(tmp_path):
    """Two real processes append profile points and flush registries
    concurrently; nothing is torn and no registry write is lost."""
    prof = str(tmp_path / "prof.jsonl")
    reg = str(tmp_path / "reg.json")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = """
import sys
sys.path.insert(0, {src!r})
from repro.core.profiler import ProfileResult
from repro.core.memory_model import fit_memory_model
from repro.profiling import LockedModelRegistry, ProfileStore
tag = sys.argv[1]
store = ProfileStore({prof!r})
reg = LockedModelRegistry({reg!r})
sizes = [2e9, 4e9, 6e9, 8e9, 1e10]
for i in range(60):
    store.put(f"{{tag}}-{{i}}", float(i + 1),
              ProfileResult(float(i + 1), 1.0, 0.0, 0.1))
    if i % 10 == 0:
        m = fit_memory_model(sizes, [2 * s + 1e9 for s in sizes])
        reg.put(f"{{tag}}-model-{{i}}", m, defer_save=True)
        reg.flush()
""".format(src=src, prof=prof, reg=reg)
    procs = [subprocess.Popen([sys.executable, "-c", code, tag])
             for tag in ("a", "b")]
    for p in procs:
        assert p.wait() == 0
    # every JSONL row parses; both writers' rows all landed
    rows = [json.loads(line) for line in open(prof)]
    assert len(rows) == 120
    fresh = ProfileStore(prof)
    assert len(fresh) == 120
    assert fresh.get("a-0", 1.0) is not None
    assert fresh.get("b-59", 60.0) is not None
    # registry kept both processes' models (merge-on-flush, no lost writes)
    merged = LockedModelRegistry(reg)
    for tag in ("a", "b"):
        for i in (0, 50):
            assert f"{tag}-model-{i}" in merged, merged.signatures()


def test_two_service_processes_allocate_against_one_store(tmp_path):
    """Acceptance: two concurrent AllocationService *processes* over one
    ProfileStore + LockedModelRegistry complete all allocations with no
    lock errors, and neither process's registry writes are lost."""
    prof = str(tmp_path / "prof.jsonl")
    reg = str(tmp_path / "reg.json")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = """
import sys
sys.path.insert(0, {src!r})
from repro.allocator import AllocationRequest, AllocationService
from repro.core.catalog import aws_like_catalog
from repro.core.simulator import (GiB, build_history, make_profile_fn,
                                  scout_like_jobs)
from repro.profiling import LockedModelRegistry, ProfileStore
which = int(sys.argv[1])
jobs = scout_like_jobs()
catalog = aws_like_catalog()
history = build_history(jobs, catalog)
# overlapping halves: [0..9] vs [6..15] -> contention on 4 signatures
mine = jobs[:10] if which == 0 else jobs[6:]
with AllocationService(catalog, history,
                       registry=LockedModelRegistry({reg!r}),
                       store=ProfileStore({prof!r}),
                       adaptive=True) as svc:
    for j in mine:
        full = j.dataset_gib * GiB
        r = svc.allocate(AllocationRequest(j.name, make_profile_fn(j),
                                           full, anchor=full * 0.01),
                         timeout=120)
        assert r.selection is not None
print("DONE", which)
""".format(src=src, prof=prof, reg=reg)
    procs = [subprocess.Popen([sys.executable, "-c", code, str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in (0, 1)]
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-3000:]
        assert "DONE" in out
    # every confident-linear signature either process saw is registered
    merged = LockedModelRegistry(reg)
    jobs = scout_like_jobs()
    for j in jobs:
        if j.mem_profile == "linear":
            assert j.name in merged, (j.name, merged.signatures())
    # the shared profile JSONL is uncorrupted
    for line in open(prof):
        json.loads(line)


def test_file_lock_times_out_instead_of_deadlocking(tmp_path):
    path = str(tmp_path / "x.lock")
    with FileLock(path):
        with pytest.raises(TimeoutError):
            # same-process second fd: flock blocks -> bounded wait
            FileLock(path, timeout_s=0.2).acquire()


# -- service / crispy / endpoint wiring ---------------------------------------


@pytest.fixture(scope="module")
def corpus():
    jobs = scout_like_jobs()
    catalog = aws_like_catalog()
    return jobs, catalog, build_history(jobs, catalog)


def _req(job, **kw):
    full = job.dataset_gib * GiB
    return AllocationRequest(job.name, make_profile_fn(job), full,
                             anchor=full * 0.01, **kw)


def test_service_adaptive_uses_fewer_points(corpus, tmp_path):
    jobs, catalog, history = corpus
    linear = [j for j in jobs
              if j.mem_profile == "linear"][:3]
    fixed_req = {}
    with AllocationService(catalog, history,
                           registry=ModelRegistry()) as svc_fixed:
        for j in linear:
            fixed_req[j.name] = svc_fixed.allocate(
                _req(j)).requirement_gib
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           adaptive=True) as svc:
        for j in linear:
            r = svc.allocate(_req(j))
            assert r.source == "zoo"
            assert r.early_stop
            assert r.profiled < 5            # strictly fewer than the ladder
            drift = abs(r.requirement_gib - fixed_req[j.name]) \
                / fixed_req[j.name]
            assert drift < 0.05              # within 5% of the fixed ladder
        assert svc.stats.adaptive_plans == len(linear)
        assert svc.stats.early_stops == len(linear)
        assert svc.stats.points_saved >= 2 * len(linear)


def test_service_budget_exhaustion_falls_back(corpus):
    jobs, catalog, history = corpus
    km = jobs[2]
    budget = ProfilingBudget(max_points=2)
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           adaptive=True, budget=budget) as svc:
        r = svc.allocate(_req(km))
        assert r.budget_exhausted
        assert r.source in ("classifier", "baseline")
        assert r.selection is not None       # still answered
        assert svc.stats.budget_denied >= 1


def test_budget_exhausted_plan_is_not_sticky(corpus):
    """A plan cut short by the budget must not be served from the negative
    plan cache once the budget recovers."""
    jobs, catalog, history = corpus
    km = jobs[2]
    budget = ProfilingBudget(max_points=2)
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           adaptive=True, budget=budget) as svc:
        first = svc.allocate(_req(km))
        assert first.budget_exhausted
        assert first.source in ("classifier", "baseline")
        budget.refund(2)                     # budget recovers
        again = svc.allocate(_req(km))
        assert not again.budget_exhausted    # re-planned, not cache-served
        assert again.source == "zoo"         # and now profiles to success


def test_exhausted_budget_still_serves_cached_points(corpus, tmp_path):
    """An exhausted budget never denies points that are already in the
    shared store — cached work is free."""
    jobs, catalog, history = corpus
    km = jobs[2]
    path = str(tmp_path / "prof.jsonl")
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           store=ProfileStore(path)) as warm:
        warm.allocate(_req(km))              # populates the store
    dead = ProfilingBudget(max_points=0)
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           store=ProfileStore(path), adaptive=True,
                           budget=dead) as svc:
        r = svc.allocate(_req(km))
        assert r.source == "zoo"             # full plan from cached points
        assert r.profiled == 0
        assert not r.budget_exhausted
        assert svc.stats.store_hits >= 3


def test_service_shared_store_skips_sibling_profiles(corpus, tmp_path):
    """Points profiled by one service are store-hits for the next (the
    restart / sibling-process path)."""
    jobs, catalog, history = corpus
    km = jobs[2]
    path = str(tmp_path / "prof.jsonl")
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           store=ProfileStore(path)) as svc1:
        svc1.allocate(_req(km))
        assert svc1.stats.profile_calls == 5
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           store=ProfileStore(path)) as svc2:
        r = svc2.allocate(_req(km))
        assert svc2.stats.profile_calls == 0
        assert svc2.stats.store_hits == 5
        assert r.profiled == 0 and r.cache_hits == 5


def test_service_persisted_anchor_shapes_repeat_ladders(corpus, tmp_path):
    jobs, catalog, history = corpus
    km = jobs[2]
    store = ProfileStore(str(tmp_path / "prof.jsonl"))
    anchor = km.dataset_gib * GiB * 0.02
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           store=store) as svc:
        svc.allocate(_req(km, anchor=None, sizes=None) if False
                     else AllocationRequest(km.name, make_profile_fn(km),
                                            km.dataset_gib * GiB,
                                            anchor=anchor))
        assert store.get_anchor(km.name) == anchor
        # anchor-less repeat reuses the persisted anchor -> same ladder ->
        # pure cache hits, zero fresh profiling
        r = svc.allocate(AllocationRequest(km.name, make_profile_fn(km),
                                           km.dataset_gib * GiB))
        assert r.profiled == 0


def test_service_executor_concurrent_signatures(corpus):
    jobs, catalog, history = corpus
    with ProfilingExecutor(max_workers=4) as ex:
        with AllocationService(catalog, history, registry=ModelRegistry(),
                               executor=ex, batch_window_s=0.05) as svc:
            futs = [svc.submit(_req(j)) for j in jobs[:6]]
            rs = [f.result(timeout=120) for f in futs]
            assert all(r.selection is not None for r in rs)
            # dedup still holds under concurrent group planning
            assert svc.stats.profile_calls <= 5 * 6


def test_crispy_allocator_adaptive_path(corpus):
    from repro.allocator.model_zoo import zoo_fitter
    jobs, catalog, history = corpus
    km = jobs[2]
    full = km.dataset_gib * GiB
    alloc = CrispyAllocator(catalog, history, overhead_per_node_gib=2.0,
                            fitter=zoo_fitter())
    fixed = alloc.allocate(km.name, make_profile_fn(km), full,
                           anchor=full * 0.01)
    adapt = alloc.allocate(km.name, make_profile_fn(km), full,
                           anchor=full * 0.01, adaptive=True)
    assert adapt.early_stop
    assert adapt.points_profiled < fixed.points_profiled == 5
    assert adapt.model.confident
    drift = abs(adapt.requirement_gib - fixed.requirement_gib) \
        / fixed.requirement_gib
    assert drift < 0.05
    # passing only a budget also routes through the scheduler
    b = ProfilingBudget(max_points=2)
    cut = alloc.allocate(km.name, make_profile_fn(km), full,
                         anchor=full * 0.01, budget=b)
    assert cut.budget_exhausted and cut.points_profiled == 2


def test_endpoint_wire_and_stats_surface_adaptive_fields(corpus):
    jobs, catalog, history = corpus
    km = jobs[2]
    budget = ProfilingBudget(max_points=50)
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           adaptive=True, budget=budget) as svc:
        ep = AllocationEndpoint(svc)
        wire = ep.handle(job=km.name, profile_at=make_profile_fn(km),
                         full_size=km.dataset_gib * GiB,
                         anchor=km.dataset_gib * GiB * 0.01)
        assert wire["early_stop"] is True
        assert wire["escalated"] is False
        assert wire["budget_exhausted"] is False
        assert wire["profiled"] < 5
        stats = ep.stats()
        assert stats["adaptive_plans"] == 1
        assert stats["early_stops"] == 1
        assert stats["points_saved"] >= 2
        assert stats["budget"]["points_spent"] == wire["profiled"]


def test_request_level_adaptive_override(corpus):
    """adaptive=False service, adaptive=True request (and vice versa)."""
    jobs, catalog, history = corpus
    nb = jobs[0]
    with AllocationService(catalog, history,
                           registry=ModelRegistry()) as svc:
        r = svc.allocate(_req(nb, adaptive=True))
        assert r.early_stop and r.profiled < 5
    with AllocationService(catalog, history, registry=ModelRegistry(),
                           adaptive=True) as svc:
        r = svc.allocate(_req(nb, adaptive=False))
        assert not r.early_stop and r.profiled == 5


def test_integer_ladder_clamps_small_anchor():
    """Regression: the anchor <= lo branch returned the anchor unclamped
    (dead `* 0 or` expression) — 0/negative anchors leaked through."""
    assert integer_ladder(0) == [1]
    assert integer_ladder(-4) == [1]
    assert integer_ladder(1) == [1]
    assert integer_ladder(3, lo=8) == [3]
    assert integer_ladder(40) == [1, 11, 20, 30, 40]
