"""CPU tests of the per-layer metrics read from the program's own spans
(`chipbench/ring.py`, `lower_s`, `compile_s`, `host_ms.decode`,
`host_bound_idle.decode`): each reader on a hand-built span ring and
trace, with the numbers worked by hand, and on a ring that lost part of
the window or a program without such spans; then on the spans of a tiny
decode and decide window."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from chipbench import ring as ring_mod  # noqa: E402
from chipbench.harness import (Bench, Cell, load_module,  # noqa: E402
                               metric_reader)
from repro.telemetry import spans  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def reader(name):
    return load_module(metric_reader(name), f"t_{name.replace('.', '_')}")


def mk(name, t0, wall, children=(), **attrs):
    s = spans.Span(name, dict(attrs))
    s.mono_start, s.wall_s = t0, wall
    s.children = list(children)
    return s


@pytest.fixture
def ring(monkeypatch):
    r = spans.TraceRing(cap=64)
    monkeypatch.setattr(spans, "_default_ring", r)
    return r


def bench(t_start, t_end, trace=None):
    return SimpleNamespace(t_start=t_start, t_end=t_end,
                           load_trace=lambda: trace)


# -- the decide cell: the planner's spans ----------------------------------------

def point(t0, lower, compile_wall, compile_s):
    """One fresh ladder point, as the planner nests it under a decision."""
    return mk("planner.profile", t0, lower + compile_wall + 0.01, [
        mk("planner.lower", t0, lower, jaxpr_s=0.1, mlir_s=0.2),
        mk("planner.compile", t0 + lower, compile_wall,
           compile_s=compile_s, compiles=1),
        mk("planner.memory", t0 + lower + compile_wall, 0.01)])


def decision(t0, *points):
    return mk("service.plan", t0, 5.0,
              [mk("pipeline.acquire", t0, 4.9, list(points))])


@pytest.fixture
def decide_ring(ring):
    # window [100, 110]: the set-up's point (99.1) and a decision after
    # the window (111) are not read
    for root in (decision(99.0, point(99.1, 0.4, 1.2, 1.1)),
                 decision(101.0, point(101.0, 0.5, 1.3, 1.0),
                          point(102.8, 0.3, 1.1, 0.9)),
                 decision(111.0, point(111.0, 9.0, 9.0, 9.0))):
        ring.record(root)
    return ring


def test_lower_and_compile_by_hand(decide_ring):
    b = bench(100.0, 110.0)
    assert reader("lower_s").read(b, None) == pytest.approx((0.5 + 0.3) / 2)
    assert reader("compile_s").read(b, None) == pytest.approx((1.0 + 0.9) / 2)
    got = ring_mod.window_spans(b, "planner.compile")
    assert [s.mono_start for s in got] == [101.5, 103.1]


def test_a_ring_that_lost_part_of_the_window_reads_nothing(monkeypatch):
    ring = spans.TraceRing(cap=2)
    monkeypatch.setattr(spans, "_default_ring", ring)
    for t0, at in ((99.0, 99.0), (101.0, 101.0), (104.0, 104.5)):
        ring.record(decision(t0, point(at, 0.5, 1.0, 0.9)))
    # the root of 99.0 fell off; it ended at 104.0, inside the window
    assert ring.evicted_until == 104.0
    assert reader("lower_s").read(bench(100.0, 110.0), None) is None
    assert reader("compile_s").read(bench(100.0, 110.0), None) is None
    # a window that opens after the lost root ended reads what it holds
    assert reader("lower_s").read(bench(104.2, 110.0), None) == 0.5
    assert reader("compile_s").read(bench(104.2, 110.0), None) == 0.9


class OldSpan:
    """A span of a program whose spans keep no monotonic start."""

    def __init__(self, name, children=()):
        self.name, self.children, self.attrs = name, list(children), {}
        self.wall_s = 1.0


def test_a_program_without_such_spans_reads_nothing(ring):
    ring.record(OldSpan("pipeline.acquire", [OldSpan("planner.lower")]))
    ring.record(OldSpan("bench.tick", [OldSpan("engine.fetch")]))
    b = bench(0.0, 1e12, {"/device:TPU:0": {"XLA Ops": [("f", 0, 1)]},
                          "/host:CPU": {"t": [("bench.window", 0, 10)]}})
    for name in ("lower_s", "compile_s", "host_ms.decode",
                 "host_bound_idle.decode"):
        assert reader(name).read(b, None) is None


def test_compile_s_needs_the_compile_listener(ring):
    ring.record(mk("planner.compile", 101.0, 1.2))
    assert reader("compile_s").read(bench(100.0, 110.0), None) is None


# -- the decode cell: the engine's spans ------------------------------------------

def tick(t0, wall, fetch_at, fetch_wall):
    return mk("engine.tick", t0, wall, [
        mk("engine.admit", t0, 0.001),
        mk("engine.dispatch", t0 + 0.001, fetch_at - t0 - 0.001),
        mk("engine.fetch", fetch_at, fetch_wall),
        mk("engine.sample", fetch_at + fetch_wall, 0.001)],
        slots=4, tokens=1)


# window: 10.0-10.1 s on the host's monotonic clock; the trace's
# bench.window annotation spans 1e6-201e6 ns, so the trace clock runs at
# 2e9 ns a host second here (a slew far beyond a real one, to show that
# the map takes both ends): t -> 1e6 + (t - 10.0) * 2e9
DECODE_TRACE = {
    "/device:TPU:0": {"XLA Ops": [("a", 1e6, 39e6), ("b", 60e6, 60e6),
                                  ("c", 150e6, 20e6)]},
    "/host:CPU": {"python": [("bench.window", 1e6, 200e6),
                             ("bench.tick", 1e6, 60e6)]},
}


@pytest.fixture
def decode_ring(ring):
    # fetches map to 41-57e6, 101-121e6 and 161-185e6 ns; the tick
    # before the window is not read
    for t in (tick(9.97, 0.03, 9.99, 0.005),
              tick(10.0, 0.030, 10.020, 0.008),
              tick(10.030, 0.030, 10.050, 0.010),
              tick(10.060, 0.035, 10.080, 0.012)):
        ring.record(t)
    return ring


def test_host_ms_by_hand(decode_ring):
    b = bench(10.0, 10.1, DECODE_TRACE)
    # (30 - 8) + (30 - 10) + (35 - 12) ms over three ticks
    assert reader("host_ms.decode").read(b, None) == \
        pytest.approx((22 + 20 + 23) / 3)


def test_host_bound_idle_by_hand(decode_ring):
    b = bench(10.0, 10.1, DECODE_TRACE)
    to = ring_mod.to_trace(b, DECODE_TRACE)
    assert to(10.0) == 1e6 and to(10.1) == pytest.approx(201e6)
    assert to(10.020) == pytest.approx(41e6)
    # ops or a fetch cover 1-40, 41-57, 60-121 and 150-185 (e6 ns): 151
    # of 200, so 49 idle; the device alone idles 81 of 200
    got = reader("host_bound_idle.decode").read(b, None)
    assert got == pytest.approx(100 * 49 / 200)
    idle = reader("device_idle.decode").read(b, None)
    assert idle == pytest.approx(100 * 81 / 200)
    assert got <= idle


def test_host_bound_idle_needs_the_window_annotation(decode_ring):
    tr = {"/device:TPU:0": DECODE_TRACE["/device:TPU:0"]}
    assert reader("host_bound_idle.decode").read(
        bench(10.0, 10.1, tr), None) is None
    assert reader("host_bound_idle.decode").read(
        bench(10.0, 10.1, None), None) is None


# -- the readers on a tiny window of each driver ----------------------------------

@pytest.fixture
def process_ring(monkeypatch):
    """A fresh ring of the process ring's size: a window's every root."""
    r = spans.TraceRing(spans.DEFAULT_RING_CAP)
    monkeypatch.setattr(spans, "_default_ring", r)
    return r


def tiny_run(driver, arch_cfg, config, traffic, limits, seconds, seed):
    import jax
    drv = load_module(BENCH / "drivers" / f"{driver}.py", f"t_span_{driver}")
    drv.model_config = lambda c: arch_cfg
    cell = Cell(f"t.{driver}", 1, "tiny", dict(config, arch="deepseek-7b"),
                "tiny", traffic, limits, SPEC)
    b = Bench(cell, seed, seconds, False, time.monotonic(), jax.devices())
    b.watch.install()
    return b, drv.run(b)


def test_readers_on_a_tiny_decode_window(process_ring):
    from repro.configs import get_arch
    cfg = get_arch("deepseek-7b").reduced(n_layers=2, d_model=64, d_ff=128,
                                          vocab_size=2048)
    as_run = {"n_layers": 2, "d_model": 64, "n_heads": cfg.n_heads,
              "n_kv_heads": cfg.n_kv_heads, "d_ff": 128, "vocab_size": 2048,
              "norm_eps": 1e-5, "rope_theta": 10000.0}
    traffic = {"driver": "decode", "shape": "decode_32k", "slots": 2,
               "max_len": 64, "check_requests": 2,
               "requests": [[5, 24], [9, 16], [7, 20], [3, 12]],
               "client_streams": [[2, 0], [1, 3]]}
    b, out = tiny_run("decode", cfg, {"as_run": as_run}, traffic,
                      {"served_gap": 0.05}, 0.5, 2 ** 32 + 9)
    ticks = ring_mod.window_spans(b, "engine.tick")
    # one engine.tick for each tick the driver timed in the window, and
    # the tokens the spans count are the ones the driver counted
    assert len(ticks) == out.layer["ticks"]
    assert sum(t.attrs["tokens"] for t in ticks) == \
        round(out.end_to_end["decode_tok_s"] * b.window_s)
    assert not any(s.attrs.get("compiles") for t in ticks
                   for s in [t] + t.children)
    own = [t.wall_s - sum(c.wall_s for c in t.children
                          if c.name == "engine.fetch") for t in ticks]
    assert reader("host_ms.decode").read(b, out) == \
        pytest.approx(1e3 * sum(own) / len(own))


def test_readers_on_a_tiny_decide_window(process_ring):
    from repro.configs import get_arch
    cfg = get_arch("deepseek-7b").reduced(n_layers=30, d_model=256, d_ff=704,
                                          vocab_size=4096)
    as_run = {"n_layers": 30, "d_model": 256, "n_heads": cfg.n_heads,
              "n_kv_heads": cfg.n_kv_heads, "d_ff": 704, "vocab_size": 4096}
    traffic = {"driver": "decide", "shape": "decode_32k",
               "seq_len": [64, 64, 64], "batch": [2, 2], "warmup": [40, 1]}
    limits = {"decide_gap": 1e-9, "bytes_floor": 1.0, "slope_floor": 1.0,
              "cache_floor": 1.0}
    b, out = tiny_run("decide", cfg, {"as_run": as_run, "stored_bytes": {
        "param": 4, "kv": 2}}, traffic, limits, 600.0, 9)
    lay = out.layer
    assert lay["decisions"] == 1 and lay["fresh"] == 5

    def under(root, name, inside=False):
        inside = inside or root.name == "pipeline.acquire"
        got = [root] if root.name == name and inside else []
        return got + [x for c in root.children for x in under(c, name, inside)]

    # every ladder point of the window is a planner.profile inside the
    # decision's pipeline.acquire, with its compile on planner.compile
    profiles = [p for r in process_ring.traces()
                for p in under(r, "planner.profile")
                if p.mono_start >= b.t_start]
    assert len(profiles) == len(ring_mod.window_spans(b, "planner.profile"))
    assert len(profiles) == lay["profile_count"] == 5
    lower = ring_mod.window_spans(b, "planner.lower")
    comp = ring_mod.window_spans(b, "planner.compile")
    assert all(c.attrs["compiles"] == 1 for c in comp)
    lower_s = reader("lower_s").read(b, out)
    compile_s = reader("compile_s").read(b, out)
    compile_wall = sum(c.wall_s for c in comp) / len(comp)
    assert lower_s == pytest.approx(sum(x.wall_s for x in lower) / 5)
    assert 0 < compile_s <= compile_wall
    # a point's wall is its lowering and its compile, and little else
    point = lay["profile_sum"] / lay["profile_count"]
    assert lower_s + compile_wall == pytest.approx(point, rel=0.05)
