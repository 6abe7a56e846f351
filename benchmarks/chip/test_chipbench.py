"""CPU tests of the chip benchmark: its files and names, the trace
reduction, the operation and byte counts, the seeded weights, and each
driver end to end at a tiny size, with its control and its planted faults
coming out not correct. No test here describes a TPU."""
from __future__ import annotations

import json
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from chipbench import counts, refplanner, tracing, weights  # noqa: E402
from chipbench.harness import (Bench, Cell, load_module,  # noqa: E402
                               metric_reader)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


# -- BENCHMARK.json and the files it names -----------------------------------

def test_benchmark_json_keys_names_and_units():
    assert set(SPEC) == TOP
    assert len(json.dumps(SPEC)) < 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(SPEC["command"]) <= 32
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["why"])
        assert LINE.match(c["source"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert LINE.match(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_finds_its_files():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        cell = Cell.find(SPEC, w["name"])
        assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
        mine = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in mine and len(mine) >= 2
        layer = cell.per_layer()
        assert layer
        for m in layer:
            assert m["moves"] in mine and m["moves"] in e2e
            assert metric_reader(m["name"]).is_file()


# -- the trace reduction -----------------------------------------------------

def small_trace():
    """Two devices and a host: ops of 10 ns units, one collective that
    overlaps compute for half its length, two program runs."""
    return {
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 100, 100), ("all-reduce.2", 150, 100),
                        ("fusion.3", 400, 50), ("fusion.1", 600, 100)],
            "XLA Modules": [("jit_step(7)", 100, 350), ("jit_step(7)", 600, 100),
                            ("jit_other(2)", 50, 10)]},
        "/device:TPU:1": {
            "XLA Ops": [("fusion.1", 100, 300)]},
        "/host:CPU": {"python": [("bench.window", 0, 1000),
                                 ("bench.tick", 0, 400),
                                 ("bench.tick", 400, 600)]},
    }


def test_trace_reduction_by_hand():
    tr = small_trace()
    lo, hi = tracing.window_bounds(tr)
    assert (lo, hi) == (0, 1000)
    assert tracing.device_planes(tr) == ["/device:TPU:0", "/device:TPU:1"]
    # device 0 busy 100..250, 400..450, 600..700 = 300; device 1 300
    assert tracing.busy_s(tr, lo, hi) == pytest.approx(300e-9)
    ops = tracing.op_seconds(tr, lo, hi)
    # device 0: the all-reduce takes 150..200 from the first fusion.1
    assert ops["fusion.1"] == pytest.approx((50 + 100 + 300) / 2 * 1e-9)
    assert sum(ops.values()) == pytest.approx(300e-9)
    # the all-reduce 150..250 overlaps fusion.1 until 200: 50 exposed
    assert tracing.exposed_collective_s(tr, lo, hi, ["/device:TPU:0"]) == \
        pytest.approx(50e-9)
    assert tracing.module_seconds(tr, lo, hi) == {
        "jit_step": pytest.approx(450e-9), "jit_other": pytest.approx(10e-9)}
    # idle 0..100 and 250..400 under the first tick, 450..600 and
    # 700..1000 under the second
    assert tracing.idle_gaps(tr, lo, hi) == [
        ("bench.tick (gaps=4)", pytest.approx(700e-9))]


def test_trace_reduction_on_a_recorded_tick():
    """One tick of the decode cell as a v5e's profiler recorded it (op
    names shortened): 431 ops, the layer scan a `while` holding its body's
    ops, one run of the engine's step program."""
    tr = json.loads((BENCH / "testdata" / "trace_v5e_decode_tick.json")
                    .read_text())
    lo, hi = tracing.window_bounds(tr)
    busy = tracing.busy_s(tr, lo, hi)
    ops = tracing.op_seconds(tr, lo, hi)
    assert sum(ops.values()) == pytest.approx(busy, rel=1e-9)
    loop = next(k for k in ops if k.startswith("while"))
    nested = next(e for e in tracing.ops(tr, "/device:TPU:0")
                  if e[0] == loop)
    assert ops[loop] < 0.01 * nested[2] / 1e9
    mods = tracing.module_seconds(tr, lo, hi)
    step = max(mods, key=mods.get)
    assert step == "jit__lambda" and mods[step] == pytest.approx(busy,
                                                                 rel=1e-3)
    gaps = tracing.idle_gaps(tr, lo, hi)
    assert [g[0].split(" ")[0] for g in gaps] == ["bench.tick"]
    assert gaps[0][1] == pytest.approx((hi - lo) / 1e9 - busy, rel=1e-9)


def test_trace_reduction_clips_to_window():
    tr = small_trace()
    assert tracing.busy_s(tr, 120, 420, ["/device:TPU:0"]) == \
        pytest.approx((250 - 120 + 20) * 1e-9)
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


# -- counts ------------------------------------------------------------------

DENSE = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "d_ff": 16, "vocab_size": 10, "mlp_kind": "swiglu"}


def test_dense_counts_by_hand():
    # attention: q 8x8, k 8x4, v 8x4, o 8x8 = 192; mlp 3 x 8 x 16 = 384
    assert counts.dense_layer_params(DENSE) == 576
    assert counts.decode_flops_per_token(DENSE) == 2 * (2 * 576 + 80)
    # params read: 2 x (576 + 16 norm) + head 80 + final norm 8 = 1272
    # kv per position: 2 layers x (k + v) x 1 head x 4 = 16 values
    assert counts.decode_step_bytes(DENSE, 4, 2, 10) == 1272 * 4 + 10 * 16 * 2


# -- seeded weights ----------------------------------------------------------

def test_weights_rows_match_the_whole_tree():
    import jax
    abstract = {"embed": jax.ShapeDtypeStruct((6, 4), np.float32),
                "layers": {"w": jax.ShapeDtypeStruct((3, 4, 5), np.float32),
                           "ln1": jax.ShapeDtypeStruct((3, 4), np.float32)}}
    kd = weights.key_data(2 ** 40 + 3)
    tree = weights.make_params(kd, abstract, 4)
    row = weights.leaf_rows(kd, "layers/w", (3, 4, 5), 4, rows=[1])
    np.testing.assert_array_equal(np.asarray(tree["layers"]["w"][1]),
                                  np.asarray(row[0]))
    assert np.all(np.asarray(tree["layers"]["ln1"]) == 1.0)
    emb = np.asarray(tree["embed"])
    assert abs(emb.std() - 0.5) < 0.3          # std 1/sqrt(d_model)
    other = weights.make_params(weights.key_data(2 ** 40 + 4), abstract, 4)
    assert not np.array_equal(emb, np.asarray(other["embed"]))


# -- the planner reference ---------------------------------------------------

CATALOG = [("a", 16, 16.0, 19.2), ("b", 32, 16.0, 38.4), ("c", 16, 32.0, 51.5)]


def test_refplanner_line_and_cheapest_fit():
    """The reference, and its float32 control at the byte counts a ladder
    point of the real cell has (some 1e10, not exact in float32)."""
    pts = [(2, 4e9), (3, 5e9), (4, 6e9), (6, 8e9), (7, 9e9)]
    ref = refplanner.decide(pts, CATALOG, 1.25, 30)
    assert ref["requirement_gib"] == pytest.approx(32e9 / 2 ** 30)
    assert ref["config"] == "a"
    big = refplanner.decide([(p[0], 10 * p[1]) for p in pts], CATALOG, 1.25,
                            30)
    assert big["config"] == "b"      # 298 GiB: b and c hold it, b is cheaper
    huge = refplanner.decide([(p[0], 20 * p[1]) for p in pts], CATALOG,
                             1.25, 30)
    assert huge["config"] == "c"     # none holds 596 GiB: the largest
    odd = [(d, b + 123457 * d * d + 7) for d, b in pts]
    lo = refplanner.decide(odd, CATALOG, 1.25, 30, np.float32)
    hi = refplanner.decide(odd, CATALOG, 1.25, 30)
    assert 0 < refplanner.gap(lo, hi) < 1e-5


# -- the drivers at a tiny size on the CPU -----------------------------------

def tiny_cell(name, arch, as_run, traffic, limits):
    cell = Cell(name, 1, "tiny", {"arch": arch, "as_run": as_run}, "tiny",
                traffic, limits, SPEC)
    return cell


def tiny_bench(cell, seed, seconds):
    import jax
    b = Bench(cell, seed, seconds, False, time.monotonic(), jax.devices())
    b.watch.install()
    return b


@pytest.fixture(scope="module")
def dense_tiny():
    from repro.configs import get_arch
    cfg = get_arch("deepseek-7b").reduced(n_layers=2, d_model=64, d_ff=128,
                                          vocab_size=2048)
    as_run = {"n_layers": 2, "d_model": 64, "n_heads": cfg.n_heads,
              "n_kv_heads": cfg.n_kv_heads, "d_ff": 128, "vocab_size": 2048,
              "norm_eps": 1e-5, "rope_theta": 10000.0}
    return cfg, as_run


def decode_driver(cfg):
    drv = load_module(BENCH / "drivers" / "decode.py", "t_decode")
    drv.model_config = lambda c: cfg
    return drv


DECODE_TRAFFIC = {"driver": "decode", "shape": "decode_32k", "slots": 2,
                  "max_len": 64, "check_requests": 3,
                  "requests": [[5, 24], [9, 16], [7, 20], [3, 12]],
                  "client_streams": [[2, 0], [1, 3]]}
DECODE_LIMIT = {"served_gap": 0.05}


def run_decode(dense_tiny, seed=2 ** 32 + 5, **patch):
    cfg, as_run = dense_tiny
    drv = decode_driver(cfg)
    for k, v in patch.items():
        setattr(drv, k, v(drv))
    cell = tiny_cell("t.decode", "deepseek-7b", as_run, DECODE_TRAFFIC,
                     DECODE_LIMIT)
    b = tiny_bench(cell, seed, 0.5)
    return drv, b, drv.run(b)


@pytest.fixture(scope="module")
def decode_ok(dense_tiny):
    return run_decode(dense_tiny)


def test_decode_cell_is_correct(decode_ok):
    _, b, out = decode_ok
    assert all(c.ok for c in out.checks), out.checks
    assert out.end_to_end["decode_tok_s"] > 0
    assert out.layer["ticks"] > 0 and out.failed == 0


def test_decode_control_is_not_correct(decode_ok):
    """The reference one precision lower (fp8) in the program's place."""
    drv, b, out = decode_ok
    gap, _ = drv.served_gaps(b, b.cell.config["as_run"], out.kept["sample"],
                             "fp8", against=out.kept["reference"])
    assert gap.max() > DECODE_LIMIT["served_gap"]


def test_decode_fault_token_altered(dense_tiny):
    """A served token altered where the engine produces it."""
    def patch(drv):
        orig = drv.check_sample

        def altered(bench, finished, k):
            sample = orig(bench, finished, k)
            r = sample[0]
            r.out_tokens[-1] = (r.out_tokens[-1] + 1) % 2048
            return sample
        return altered
    _, _, out = run_decode(dense_tiny, check_sample=patch)
    assert not all(c.ok for c in out.checks)


def test_decode_fault_step_keeps_its_state(dense_tiny):
    """A decode step that returns its caches unchanged."""
    def patch(drv):
        orig = drv.build

        def build(bench):
            cfg, eng = orig(bench)
            step = eng._step
            eng._step = lambda p, b, c: (step(p, b, c)[0], c)
            return cfg, eng
        return build
    _, _, out = run_decode(dense_tiny, build=patch)
    assert not all(c.ok for c in out.checks)


def test_decode_clients_take_their_own_streams(dense_tiny):
    """Each client cycles through its own entries, whatever the others
    do, and the same seed draws the same prompts."""
    cfg, as_run = dense_tiny
    drv = decode_driver(cfg)
    cell = tiny_cell("t.decode", "deepseek-7b", as_run, DECODE_TRAFFIC,
                     DECODE_LIMIT)
    b = tiny_bench(cell, 2 ** 31 + 11, 0.1)
    table = DECODE_TRAFFIC["requests"]
    streams = drv.client_streams(b, 2048)
    got = [[next(s) for _ in range(3)] for s in streams]
    for reqs, entries in zip(got, DECODE_TRAFFIC["client_streams"]):
        assert [(len(r.prompt), r.max_new_tokens) for r in reqs] == \
            [tuple(table[i]) for i in entries + entries[:1]]
    again = next(drv.client_streams(b, 2048)[1])
    assert again.prompt == got[1][0].prompt
    assert got[0][0].prompt != got[0][2].prompt


# two fresh jobs, each decided whatever the CPU's speed: the window ends
# when the grid is spent
DECIDE_TRAFFIC = {"driver": "decide", "shape": "decode_32k",
                  "seq_len": [64, 128, 64], "batch": [2, 2], "warmup": [40, 1]}
DECIDE_LIMITS = {"decide_gap": 1e-9, "bytes_floor": 1.0, "slope_floor": 1.0,
                 "cache_floor": 1.0}


def run_decide(wrap=None):
    from repro.configs import get_arch
    # the real cell's proportions: the embedding and head some four
    # layers' worth of parameters, the cache a tenth of a layer
    cfg = get_arch("deepseek-7b").reduced(n_layers=30, d_model=256, d_ff=704,
                                          vocab_size=4096)
    drv = load_module(BENCH / "drivers" / "decide.py", "t_decide")
    drv.model_config = lambda c: cfg
    if wrap is not None:
        wrap(drv)
    as_run = {"n_layers": 30, "d_model": 256, "n_heads": cfg.n_heads,
              "n_kv_heads": cfg.n_kv_heads, "d_ff": 704, "vocab_size": 4096}
    cell = tiny_cell("t.decide", "deepseek-7b", as_run, DECIDE_TRAFFIC,
                     DECIDE_LIMITS)
    cell.config["stored_bytes"] = {"param": 4, "kv": 2}
    return drv, drv.run(tiny_bench(cell, 9, 600.0))


@pytest.fixture(scope="module")
def decide_ok():
    return run_decide()


def test_decide_cell_is_correct(decide_ok):
    _, out = decide_ok
    assert all(c.ok for c in out.checks), out.checks
    assert {c.name for c in out.checks} == set(DECIDE_LIMITS)
    assert out.layer["fresh"] == 5 * out.layer["decisions"]


def test_decide_fault_answer_altered():
    def alter(drv):
        from repro.serve.engine import AllocationEndpoint
        orig = AllocationEndpoint.to_wire

        def to_wire(resp):
            w = orig(resp)
            w["requirement_gib"] *= 1 + 1e-6
            return w
        drv_run = drv.run

        def run(bench):
            AllocationEndpoint.to_wire = staticmethod(to_wire)
            try:
                return drv_run(bench)
            finally:
                AllocationEndpoint.to_wire = staticmethod(orig)
        drv.run = run
    _, bad = run_decide(alter)
    assert not all(c.ok for c in bad.checks)


@pytest.fixture(scope="module")
def control():
    return load_module(BENCH / "control.py", "t_control")


@pytest.mark.parametrize("fault", ["depth_fixed", "shape_fixed"])
def test_decide_fault_in_the_profile(control, fault):
    """A fault of the profile layer that the floors counted from the
    configuration catch. (`depth_short` shows only at the real cell's
    sizes, where the layers outweigh the step's temporaries: it is read on
    the chip.)"""
    def plant(drv):
        drv_run = drv.run

        def run(bench):
            with control.planted(fault, DECIDE_TRAFFIC):
                return drv_run(bench)
        drv.run = run
    _, bad = run_decide(plant)
    assert not all(c.ok for c in bad.checks), bad.checks


def test_ladder_floors_by_hand():
    c = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "d_ff": 8,
         "vocab_size": 10, "mlp_kind": "swiglu"}
    stored = {"param": 4, "kv": 2}
    # layer: q 16 + k 8 + v 8 + o 16 + mlp 96 + norms 8 = 152 params
    # cache: 2 x (1 head x 2) values x 2 bytes = 8 bytes a token
    fixed, layer = refplanner.profile_floor(c, 3, 2, stored)
    assert (fixed, layer) == ((80 + 4) * 4, 152 * 4 + 6 * 8)
    pts = [(d, 2 * (fixed + d * layer)) for d in (2, 3, 4)]
    pts2 = [(d, 2 * (fixed + d * refplanner.profile_floor(c, 5, 2, stored)[1]))
            for d in (2, 3, 4)]
    got = refplanner.ladder_floors([(3, 2, pts), (5, 2, pts2)], c, stored)
    assert got == pytest.approx({"bytes_floor": 0.5, "slope_floor": 0.5,
                                 "cache_floor": 0.5})
    assert refplanner.ladder_floors([(3, 2, pts)], c, stored)[
        "cache_floor"] is None
    flat = [(d, 2 * (fixed + 3 * layer)) for d in (2, 3, 4)]
    assert refplanner.ladder_floors([(3, 2, flat)], c, stored)[
        "slope_floor"] > 1e6


def test_controls_are_judged_by_the_cells_limits(control):
    from chipbench.harness import Check
    v = control.verdict([Check("served_gap", 0.3, 0.14),
                         Check("other", 0.0, 1.0)])
    assert v == {"checks": {"served_gap": 0.3, "other": 0.0},
                 "correct": False}
