"""Unified allocation pipeline: placement strategies (ladder vs infogain),
the staged decision path's stage contracts, the one-acquisition-rule
budget accounting (cached/stored points are never charged — including the
shared-envelope regression with two services over one daemon), and the
service-purity contract (service.py carries no ladder/fit/selection logic
of its own)."""
import math
import os
import socket
import tempfile
import zlib

import numpy as np
import pytest

from repro.allocator import AllocationRequest, AllocationService
from repro.allocator.model_zoo import fit_zoo, zoo_fitter
from repro.core.catalog import aws_like_catalog
from repro.core.crispy import CrispyAllocator
from repro.core.memory_model import fit_memory_model
from repro.core.profiler import ProfileResult
from repro.core.sampling import ladder_from_anchor
from repro.core.simulator import (GiB, build_history, make_profile_fn,
                                  scout_like_jobs)
from repro.pipeline import (AllocationPipeline, InfoGainPlacer,
                            LadderPlacer, MemoryPointCache,
                            PipelineRequest, PointSource, drive_placement,
                            make_placer)
from repro.profiling import ProfileStore, ProfilingBudget
from repro.state import CrispyDaemon, DaemonBackend

FULL = 1e11
LADDER = ladder_from_anchor(FULL * 0.01).sizes

needs_unix_sockets = pytest.mark.skipif(
    not hasattr(socket, "AF_UNIX"),
    reason="unix-domain sockets unavailable")


def _daemon_socket() -> str:
    # AF_UNIX paths are length-limited; use a short tempdir
    return os.path.join(tempfile.mkdtemp(prefix="crispyd-"), "d.sock")


def _deterministic_mem(name, mem_fn, noise):
    def mem(s):
        rng = np.random.default_rng(
            zlib.crc32(f"{name}|{round(s)}".encode()))
        return mem_fn(s) * (1.0 + rng.normal(0.0, noise))
    return mem


def _acquire_fn(mem, wall=10.0, calls=None):
    def acquire(s):
        if calls is not None:
            calls.append(s)
        return ProfileResult(s, mem(s), 0.0, wall), True
    return acquire


@pytest.fixture(scope="module")
def corpus():
    jobs = scout_like_jobs()
    catalog = aws_like_catalog()
    return jobs, catalog, build_history(jobs, catalog)


def _req(job, **kw):
    full = job.dataset_gib * GiB
    return AllocationRequest(job.name, make_profile_fn(job), full,
                             anchor=full * 0.01, **kw)


# -- placement strategies -----------------------------------------------------


def test_make_placer_resolves_names_and_instances():
    assert make_placer("infogain").name == "infogain"
    assert make_placer("ladder").name == "ladder"
    assert make_placer(None).name == "infogain"          # the default
    custom = LadderPlacer(max_extra_points=0)
    assert make_placer(custom) is custom
    with pytest.raises(ValueError):
        make_placer("bogus")
    with pytest.raises(TypeError):
        make_placer(object())


def test_infogain_seeds_cheap_then_jumps_to_separating_size():
    """Seeds are the two cheapest points (no fit to rank by yet, and the
    PR-2 cost profile must survive for single-model fitters); the first
    gain-scored choice then jumps to whichever size best separates the
    candidates — the far end of the calibrated range — and placement
    never leaves the ladder's bounds."""
    mem = _deterministic_mem("span", lambda s: 2.0 * s, 0.0)
    out = drive_placement(InfoGainPlacer(), LADDER, FULL,
                          _acquire_fn(mem), lambda a, b: fit_zoo(a, b))
    assert out.sizes[:2] == sorted(LADDER)[:2]
    assert out.sizes[2] == max(LADDER)       # the separating jump
    assert all(min(LADDER) <= s <= max(LADDER) for s in out.sizes)


def test_infogain_matches_ladder_minimum_on_clean_linear():
    """The easy case must not regress: 3 points (the LOOCV minimum),
    confident, accurate."""
    mem = _deterministic_mem("lin", lambda s: 0.9 * s + 1.6e9, 0.002)
    out = drive_placement(InfoGainPlacer(), LADDER, FULL,
                          _acquire_fn(mem), lambda a, b: fit_zoo(a, b))
    assert out.early_stop and len(out.sizes) == 3
    assert out.fit.confident
    truth = 0.9 * FULL + 1.6e9
    assert abs(out.fit.predict(FULL) - truth) / truth < 0.02


def test_infogain_beats_ladder_prefix_on_curved_jobs():
    """The tentpole claim (benchmarks/point_placement.py measures it; this
    pins it): on power-law and log-linear shapes infogain profiles
    STRICTLY fewer points at equal-or-better requirement error."""
    # the exact jobs (and therefore noise draws) of
    # benchmarks/point_placement.py's curved, gate-passing set
    cases = [("powerlaw/clean", lambda s: 3.0e-4 * s ** 1.35, 0.002),
             ("powerlaw/noisy", lambda s: 3.0e-4 * s ** 1.35, 0.01),
             ("loglinear/clean", lambda s: 4e9 * math.log(s) - 60e9, 0.002)]
    for name, mem_fn, noise in cases:
        mem = _deterministic_mem(name, mem_fn, noise)
        truth = mem_fn(FULL)
        outs = {}
        for placer in (LadderPlacer(), InfoGainPlacer()):
            outs[placer.name] = drive_placement(
                placer, LADDER, FULL, _acquire_fn(mem),
                lambda a, b: fit_zoo(a, b))
        lad, inf = outs["ladder"], outs["infogain"]
        assert len(inf.sizes) < len(lad.sizes), name
        assert inf.fit.confident, name
        inf_err = abs(inf.fit.requirement(FULL) - truth) / truth
        lad_err = abs(lad.fit.requirement(FULL) - truth) / truth
        assert inf_err <= lad_err + 0.02, name


def test_infogain_stops_early_on_hopeless_noise():
    """Gate-failing data: expected gain collapses and infogain reaches the
    fallback in fewer points than ladder-prefix + escalation."""
    mem = _deterministic_mem("noisy", lambda s: 1.1 * s, 0.09)
    inf = drive_placement(InfoGainPlacer(), LADDER, FULL,
                          _acquire_fn(mem), lambda a, b: fit_zoo(a, b))
    lad = drive_placement(LadderPlacer(), LADDER, FULL,
                          _acquire_fn(mem), lambda a, b: fit_zoo(a, b))
    assert not inf.fit.confident and not lad.fit.confident
    assert inf.fit.requirement(FULL) == 0.0      # BFA fallback downstream
    assert len(inf.sizes) < len(lad.sizes)


def test_ladder_placer_reproduces_prefix_and_escalation():
    """placement="ladder" keeps PR-2 semantics: clean jobs stop on the
    smallest-first prefix; noisy jobs escalate into gap midpoints and
    never leave the calibrated range."""
    clean = _deterministic_mem("c", lambda s: 2.0 * s, 0.0)
    out = drive_placement(LadderPlacer(), LADDER, FULL,
                          _acquire_fn(clean), lambda a, b: fit_zoo(a, b))
    assert out.early_stop
    assert out.sizes == sorted(LADDER)[:len(out.sizes)]

    noisy = _deterministic_mem("n", lambda s: s, 0.09)
    out2 = drive_placement(LadderPlacer(), LADDER, FULL,
                           _acquire_fn(noisy), lambda a, b: fit_zoo(a, b))
    assert out2.escalated and len(out2.sizes) > len(LADDER)
    assert max(out2.sizes) <= max(LADDER)


def test_placement_budget_denial_returns_partial():
    budget = ProfilingBudget(max_points=2)
    mem = _deterministic_mem("cut", lambda s: 2.0 * s, 0.0)

    def acquire(s):
        if not budget.try_spend():
            return None
        r = ProfileResult(s, mem(s), 0.0, 10.0)
        budget.charge(r.wall_s)
        return r, True

    out = drive_placement(InfoGainPlacer(), LADDER, FULL, acquire,
                          lambda a, b: fit_zoo(a, b))
    assert out.budget_exhausted and len(out.sizes) == 2
    assert not out.fit.confident             # 2 points never pass LOOCV


# -- pipeline stage contracts -------------------------------------------------


def test_pipeline_run_stages_end_to_end(corpus):
    jobs, catalog, history = corpus
    km = jobs[2]
    pipeline = AllocationPipeline(catalog, history, adaptive=True)
    full = km.dataset_gib * GiB
    trace = pipeline.run(PipelineRequest(km.name, make_profile_fn(km),
                                         full, anchor=full * 0.01))
    assert trace.plan.source == "zoo"
    assert trace.plan.placement == "infogain"
    assert trace.requirement_gib > 0
    assert trace.selection.config.usable_mem_gib(2.0) > 0
    assert trace.plan.profiled == trace.plan.total_points < 5


def test_pipeline_warm_start_skips_profiling(corpus):
    from repro.allocator import ModelRegistry
    jobs, catalog, history = corpus
    km = jobs[2]
    reg = ModelRegistry()
    pipeline = AllocationPipeline(catalog, history, registry=reg)
    full = km.dataset_gib * GiB
    preq = PipelineRequest(km.name, make_profile_fn(km), full,
                           anchor=full * 0.01)
    first = pipeline.run(preq)
    assert first.plan.source == "zoo" and first.plan.registered
    again = pipeline.run(preq)
    assert again.plan.source == "registry"
    assert again.plan.profiled == 0 and again.plan.total_points == 0
    # byte-identical answers from the model either way
    assert again.requirement_gib == first.requirement_gib
    assert again.selection.config.name == first.selection.config.name


def test_point_source_cached_points_skip_budget():
    """The one acquisition rule: cache/store hits are served before the
    budget gate and never charge the envelope."""
    budget = ProfilingBudget(max_points=1, charge_s=100.0)
    cache = MemoryPointCache()
    src = PointSource("sig", lambda s: ProfileResult(s, 2.0 * s, 0.0, 10.0),
                      budget=budget, cache=cache)
    r1 = src.acquire(1e9)
    assert r1 is not None and r1[1] is True
    assert budget.points_spent == 1 and budget.charged_s == 10.0
    # repeat: served from the cache with the budget fully exhausted
    r2 = src.acquire(1e9)
    assert r2 is not None and r2[1] is False
    assert budget.points_spent == 1 and budget.charged_s == 10.0
    assert not src.stats.denied
    # a genuinely new point is denied
    assert src.acquire(2e9) is None
    assert src.stats.denied


@needs_unix_sockets
def test_shared_daemon_budget_not_charged_for_stored_points(corpus):
    """REGRESSION (budget accounting for cached points): two services
    share one daemon — profile store, registry AND budget envelope. The
    second service answers a gate-failing job (no registry warm-start)
    entirely from the first's stored ladder: the shared envelope must not
    lose a single charged second or point for it."""
    jobs, catalog, history = corpus
    noisy = jobs[6]                          # logregression: never confident
    sock = _daemon_socket()
    with CrispyDaemon(sock):
        be = DaemonBackend(sock)
        budget_a = ProfilingBudget(charge_s=10_000.0, backend=be)
        with AllocationService(catalog, history, backend=be,
                               budget=budget_a) as a:
            ra = a.allocate(_req(noisy))
            assert ra.profiled == 5
        charged = budget_a.charged_s
        points = budget_a.points_spent
        assert charged > 0 and points == 5

        be_b = DaemonBackend(sock)
        budget_b = ProfilingBudget(charge_s=10_000.0, backend=be_b)
        with AllocationService(catalog, history, backend=be_b,
                               budget=budget_b) as b:
            rb = b.allocate(_req(noisy))
            assert rb.profiled == 0
            assert rb.cache_hits == 5        # all five from the store
        assert budget_b.charged_s == charged     # not a second charged
        assert budget_b.points_spent == points   # nor a reserved point


@needs_unix_sockets
def test_one_shot_path_with_stale_store_view_charges_nothing(corpus):
    """The bug the unified acquisition stage fixes: a CrispyAllocator
    holding a ProfileStore handle opened BEFORE a sibling profiled (stale
    local index) used to re-measure the sibling's points and charge the
    shared envelope twice. Acquisition now refreshes the store first."""
    jobs, catalog, history = corpus
    km = jobs[2]                         # clean linear: prefix stops at 3
    full = km.dataset_gib * GiB
    sock = _daemon_socket()
    with CrispyDaemon(sock):
        be = DaemonBackend(sock)
        stale = ProfileStore(backend=DaemonBackend(sock))    # empty view
        with AllocationService(catalog, history, backend=be,
                               budget=ProfilingBudget(charge_s=10_000.0,
                                                      backend=be)) as a:
            a.allocate(_req(km))         # profiles + stores the full ladder
        shared = ProfilingBudget(charge_s=10_000.0,
                                 backend=DaemonBackend(sock))
        charged = shared.charged_s
        points = shared.points_spent
        assert charged > 0

        rep = CrispyAllocator(catalog, history, overhead_per_node_gib=2.0,
                              fitter=zoo_fitter()).allocate(
            km.name, make_profile_fn(km), full, anchor=full * 0.01,
            store=stale, budget=shared, placement="ladder")
        assert rep.points_profiled == 3          # prefix from the store...
        assert rep.model.confident
        assert shared.charged_s == charged       # ...without any new charge
        assert shared.points_spent == points
        assert not rep.budget_exhausted


def test_point_source_refunds_reservation_when_profiler_raises():
    """A profile run that crashes must hand its budget reservation back:
    with a shared max_points envelope, leaked reservations from transient
    failures would drain the budget with zero points measured."""
    budget = ProfilingBudget(max_points=2)

    def boom(_s):
        raise RuntimeError("profiler crashed")

    src = PointSource("sig", boom, budget=budget)
    with pytest.raises(RuntimeError, match="profiler crashed"):
        src.acquire(1e9)
    assert budget.points_spent == 0          # reservation refunded
    ok = PointSource("sig", lambda s: ProfileResult(s, s, 0.0, 1.0),
                     budget=budget)
    assert ok.acquire(1e9) is not None       # envelope still usable
    assert ok.acquire(2e9) is not None
    assert budget.points_spent == 2


def test_infogain_with_single_model_fitter_keeps_escalation():
    """CrispyAllocator's default config (paper's OLS fitter + infogain):
    a non-zoo fit has no candidate set to rank sizes by, so placement
    must fall back to FULL ladder semantics — including gap-midpoint
    escalation for an unconfident end-of-ladder fit, exactly as PR-2's
    scheduler behaved (escalate on inf disagreement)."""
    # seed chosen so the 3-point linear fit misses the paper's R2 gate
    # (the single-model gate has no LOOCV backstop at 3 points)
    mem = _deterministic_mem("d", lambda s: s, 0.09)    # gate-failing
    out = drive_placement(InfoGainPlacer(), LADDER, FULL,
                          _acquire_fn(mem),
                          lambda a, b: fit_memory_model(a, b))
    assert out.escalated
    assert len(out.sizes) > len(LADDER)
    assert max(out.sizes) <= max(LADDER)
    assert not out.fit.confident


def test_plan_cache_is_tag_aware(corpus):
    """Tags can steer the classifier, so a cached negative plan computed
    under one tag palette must not answer a request carrying another."""
    jobs, catalog, history = corpus
    logreg = jobs[6]
    with AllocationService(catalog, history) as svc:
        first = svc.allocate(_req(logreg, tags=("format:csv",)))
        assert first.source in ("classifier", "baseline")
        hits0 = svc.stats.plan_cache_hits
        # same palette: served from the plan cache
        svc.allocate(_req(logreg, tags=("format:csv",)))
        assert svc.stats.plan_cache_hits == hits0 + 1
        # different palette: re-planned, not cache-served
        fits0 = svc.stats.zoo_fits
        svc.allocate(_req(logreg, tags=("format:parquet",)))
        assert svc.stats.plan_cache_hits == hits0 + 1
        assert svc.stats.zoo_fits == fits0 + 1


def test_plan_cache_is_settings_aware(corpus):
    """A negative plan computed under adaptive acquisition must not
    answer an explicit adaptive=False request for the same signature —
    the fixed 5-point ladder could pass the gate where the adaptive
    partial ladder did not (and vice versa)."""
    jobs, catalog, history = corpus
    linreg = jobs[4]        # noisy: unconfident at 3 adaptive points
    with AllocationService(catalog, history, adaptive=True) as svc:
        first = svc.allocate(_req(linreg))
        assert first.source in ("classifier", "baseline")
        assert first.placement == "infogain"
        assert first.profiled + first.cache_hits < 5     # stopped early
        fixed = svc.allocate(_req(linreg, adaptive=False))
        # re-planned under fixed settings: the full ladder materialized
        # (partly from the LRU), no cached adaptive plan served
        assert fixed.placement is None
        assert fixed.profiled + fixed.cache_hits == 5
        assert svc.stats.plan_cache_hits == 0


# -- service purity contract --------------------------------------------------


def test_service_contains_no_pipeline_logic():
    """service.py is batching + wire ONLY: the acquisition/fit/selection
    vocabulary must not appear — the unified pipeline is the single code
    path (the parity test in test_allocator.py checks the semantics; this
    pins the structure)."""
    import repro.allocator.service as service_mod
    src = open(service_mod.__file__).read()
    forbidden = ["fit_zoo", "fit_memory_model", "ladder_from_anchor",
                 "select_crispy", "select_like", "drive_placement",
                 "gap_midpoint", "calibrate_anchor", "model_zoo",
                 "requirement("]
    hits = [word for word in forbidden if word in src]
    assert not hits, f"service.py re-grew pipeline logic: {hits}"


def test_crispy_wrapper_contains_no_pipeline_logic():
    """core/crispy.py is a thin convenience wrapper over the pipeline."""
    import repro.core.crispy as crispy_mod
    src = open(crispy_mod.__file__).read()
    for word in ("fit_zoo", "ladder_from_anchor", "select_crispy",
                 "drive_placement", "try_spend", "store.get("):
        assert word not in src, word
