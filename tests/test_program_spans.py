"""Spans inside the program on the device trace's clock: the serve
engine's tick tree, the planner's profile tree with its compile phases
from jax.monitoring, the spans' monotonic start, their profiler twins,
the process ring's size and loss detection, and a full ring of ticks
published through the daemon."""
import dataclasses
import glob
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import jax
import pytest

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.hbm_planner import HBMPlanner, _reduced_depth
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine
from repro.state import CrispyDaemon, DaemonBackend
from repro.state.transport import MAX_FRAME_BYTES
from repro.telemetry import (MetricsRegistry, TraceRing, fleet_traces,
                             publish_traces, span)
from repro.telemetry.export import TRACES_ROW_BYTES
from repro.telemetry import spans as spans_mod
from repro.telemetry.spans import DEFAULT_RING_CAP, default_ring

RUN = RunConfig(attn_impl="full", remat="nothing", compute_dtype="float32")
COMPILE_KEYS = {"jaxpr_s", "mlir_s", "compile_s", "compiles"}


def walk(s):
    yield s
    for c in s.children:
        yield from walk(c)


def compiles_in(root) -> int:
    return sum(s.attrs.get("compiles", 0) for s in walk(root))


@pytest.fixture
def ring(monkeypatch):
    """A fresh process ring, so roots of other tests do not mix in."""
    r = TraceRing(DEFAULT_RING_CAP)
    monkeypatch.setattr(spans_mod, "_default_ring", r)
    return r


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("deepseek-7b").reduced()
    m = build_model(cfg, RUN)
    return m, m.init(jax.random.PRNGKey(0))


# -- the serve engine -----------------------------------------------------------


def test_engine_tick_tree(small_model, ring):
    m, p = small_model
    eng = ServeEngine(m, p, slots=2, max_len=32)
    for rid in range(3):
        eng.submit(Request(rid, prompt=[rid + 1, 2, 3], max_new_tokens=4))
    eng.run()
    roots = ring.traces()
    # the engine's construction prepares the weights, then every root is
    # a tick
    assert roots[0].name == "engine.prepare"
    ticks = [r for r in roots if r.name == "engine.tick"]
    assert ticks and len(ticks) == len(ring) - 1
    for t in ticks:
        assert [c.name for c in t.children] == [
            "engine.admit", "engine.dispatch", "engine.fetch",
            "engine.sample"]
        assert t.attrs["slots"] in (1, 2)
        assert sum(c.wall_s for c in t.children) <= t.wall_s
        prev = t.mono_start
        for c in t.children:
            assert c.mono_start >= prev
            assert c.mono_start + c.wall_s <= t.mono_start + t.wall_s
            prev = c.mono_start + c.wall_s
    # every output token appended is counted on the tick that appended it
    assert sum(t.attrs["tokens"] for t in ticks) == \
        sum(len(r.out_tokens) for r in eng.finished) == 12
    # the first tick compiles the step; no later tick compiles anything
    assert compiles_in(ticks[0]) >= 1
    assert "compile_s" in ticks[0].children[1].attrs     # engine.dispatch
    assert all(compiles_in(t) == 0 for t in ticks[1:])
    assert not any(COMPILE_KEYS & set(s.attrs)
                   for t in ticks[1:] for s in walk(t))


def test_engine_tick_carries_routing_for_moe_only(small_model, ring):
    """A MoE model's step also returns the pairs each MoE layer routed to
    each held expert; `engine.fetch` copies them beside the logits and the
    tick records `held_routes` and `experts_hit`. A dense model's step
    returns its logits and caches alone, as before, and its ticks carry
    neither attribute."""
    cfg = get_arch("deepseek-v3-671b-ep32").reduced()   # 4 of 8 held, top-2
    m = build_model(cfg, RUN)
    eng = ServeEngine(m, m.init(jax.random.PRNGKey(0)), slots=2, max_len=32)
    for rid in range(3):
        eng.submit(Request(rid, prompt=[rid + 1, 2, 3], max_new_tokens=4))
    eng.run()
    ticks = [r for r in ring.traces() if r.name == "engine.tick"]
    n_moe = cfg.n_layers - cfg.moe.first_dense_layers
    held = cfg.moe.held
    for t in ticks:
        # every slot of the batch is routed, active or not
        assert 0 <= t.attrs["held_routes"] <= 2 * cfg.moe.top_k * n_moe
        assert 0 <= t.attrs["experts_hit"] <= min(
            held, 2 * cfg.moe.top_k) * n_moe
        assert t.attrs["experts_hit"] <= t.attrs["held_routes"]
    assert sum(t.attrs["held_routes"] for t in ticks) > 0
    out = eng._step(eng.params, eng._batch(), eng.caches)
    assert len(out) == 3 and out[2].shape == (n_moe, held)
    assert out[2].dtype == jax.numpy.int32

    dm, dp = small_model
    dense = ServeEngine(dm, dp, slots=1, max_len=32)
    n = len(ring)
    dense.submit(Request(0, prompt=[4, 5], max_new_tokens=2))
    dense.run()
    dticks = [r for r in ring.traces()[n:] if r.name == "engine.tick"]
    assert dticks and not any({"held_routes", "experts_hit"} & set(t.attrs)
                              for t in dticks)
    assert len(dense._step(dense.params, dense._batch(), dense.caches)) == 2


def test_engine_spans_off_with_a_disabled_registry(small_model, ring):
    m, p = small_model
    eng = ServeEngine(m, p, slots=1, max_len=32,
                      telemetry=MetricsRegistry(enabled=False))
    eng.submit(Request(0, prompt=[4, 5], max_new_tokens=2))
    assert len(eng.run()) == 1
    assert len(ring) == 0


def test_engine_prepare_span(small_model, ring):
    """A root `engine.prepare` at construction and again on every
    assignment of `params`. This model computes in float32, so the step
    converts no leaf and none is cast."""
    m, p = small_model
    eng = ServeEngine(m, p, slots=1, max_len=32)
    eng.params = p
    preps = ring.traces()
    assert [r.name for r in preps] == ["engine.prepare"] * 2
    for r in preps:
        assert r.attrs["cast_leaves"] == 0 and r.attrs["cast_bytes"] == 0
        assert r.attrs["kept_leaves"] == len(jax.tree.leaves(p))
        assert r.wall_s > 0 and not r.children
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(p)))


def test_engine_step_is_named(small_model):
    m, p = small_model
    eng = ServeEngine(m, p, slots=1, max_len=32)
    assert eng._step.__name__ == "decode_step"


# -- the planner ---------------------------------------------------------------


def test_planner_profile_spans(ring):
    from jax.experimental.compilation_cache import compilation_cache as cc
    cfg = _reduced_depth(get_arch("deepseek-7b").reduced(
        d_model=64, vocab_size=256), 2)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                global_batch=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with span("pipeline.acquire"):
            per_dev = HBMPlanner().profile_memory(cfg, shape, mesh, RUN)
        with span("after"):
            pass
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert per_dev > 0
    acquire, after = ring.traces()
    (prof,) = acquire.children
    assert prof.name == "planner.profile"
    assert prof.attrs == {"n_layers": 2, "seq_len": 64, "batch": 2}
    lower, comp, mem = prof.children
    assert [lower.name, comp.name, mem.name] == [
        "planner.lower", "planner.compile", "planner.memory"]
    # tracing and lowering land on planner.lower, XLA's compile on
    # planner.compile, and nothing on memory_analysis or a later span
    assert lower.attrs["jaxpr_s"] > 0 and lower.attrs["mlir_s"] > 0
    assert "compile_s" not in lower.attrs
    assert comp.attrs["compiles"] == 1
    assert 0 < comp.attrs["compile_s"] <= comp.wall_s
    assert not COMPILE_KEYS & set(mem.attrs)
    assert not COMPILE_KEYS & set(after.attrs)
    assert not COMPILE_KEYS & (set(prof.attrs) | set(acquire.attrs))


# -- the clock and the twins ---------------------------------------------------


def test_span_start_is_on_the_monotonic_clock():
    r = TraceRing()
    before = time.monotonic()
    with span("clock", ring=r) as s:
        pass
    after = time.monotonic()
    assert before - 1e-3 <= s.mono_start <= after + 1e-3
    assert abs(s.mono_start - before) < 1e-3


def test_span_twins_on_the_profilers_host_plane(tmp_path):
    jax.numpy.ones(4).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("twin.outer", ring=TraceRing()):
            with span("twin.inner"):
                jax.numpy.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name: (e.start_ns, e.duration_ns) for plane in data.planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name.startswith("twin.")}
    assert set(host) == {"twin.outer", "twin.inner"}
    (o0, od), (i0, idur) = host["twin.outer"], host["twin.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od


def test_spans_never_import_jax():
    """The daemon stays stdlib-only: spans open and close without JAX."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import sys, repro.state.daemon\n"
            "from repro.telemetry import span\n"
            "with span('a'):\n"
            "    with span('b'):\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


# -- the ring ------------------------------------------------------------------


def _root(t0: float, wall: float):
    s = spans_mod.Span("root", {})
    s.mono_start, s.wall_s = t0, wall
    return s


def test_process_ring_holds_a_window_of_short_ticks():
    """51 s of 5 ms ticks is 10,200 roots: the process ring keeps them
    all and reports no loss."""
    r = TraceRing(DEFAULT_RING_CAP)
    for i in range(10_200):
        r.record(_root(i * 0.005, 0.004))
    assert len(r) == 10_200 and r.evicted_until is None
    assert default_ring().cap == DEFAULT_RING_CAP >= 10_200


def test_ring_reports_how_late_a_lost_root_ended():
    r = TraceRing(cap=4)
    for i in range(6):
        r.record(_root(float(i), 0.5))
    assert [s.mono_start for s in r.traces()] == [2.0, 3.0, 4.0, 5.0]
    assert r.evicted_until == 1.5
    assert r.recorded == 6
    r.clear()
    assert r.evicted_until is None and len(r) == 0


@pytest.mark.skipif(not hasattr(socket, "AF_UNIX"),
                    reason="unix-domain sockets unavailable")
def test_full_ring_of_ticks_publishes_through_the_daemon():
    """A full process ring of engine ticks is about twice the daemon's
    frame cap as one row: publishing keeps the newest roots that fit
    the row budget, and the daemon takes the row."""
    r = TraceRing(DEFAULT_RING_CAP)
    for i in range(DEFAULT_RING_CAP):
        with span("engine.tick", ring=r) as tick:
            for name in ("engine.admit", "engine.dispatch", "engine.fetch",
                         "engine.sample"):
                with span(name):
                    pass
            tick.attrs.update(slots=4, tokens=i % 5)
    roots = r.traces()
    assert len(roots) == DEFAULT_RING_CAP
    assert sum(len(json.dumps(s.to_dict())) for s in roots) > MAX_FRAME_BYTES
    assert TRACES_ROW_BYTES <= MAX_FRAME_BYTES // 2
    sock = os.path.join(tempfile.mkdtemp(prefix="crispyps-"), "d.sock")
    with CrispyDaemon(sock):
        backend = DaemonBackend(sock)
        row = publish_traces(backend, "engine", r)
        got = fleet_traces(backend)["engine"]
    assert 0 < len(got) < DEFAULT_RING_CAP
    assert len(got) + row["omitted"] == DEFAULT_RING_CAP
    assert len(json.dumps(got)) <= TRACES_ROW_BYTES
    assert [d["span_id"] for d in got] == \
        [s.span_id for s in roots[-len(got):]]
    assert all(len(d["children"]) == 4 and d["attrs"]["slots"] == 4
               for d in got)
