"""CPU tests of the DeepSeek-V3 cell's benchmark files: the driver end to
end at a tiny size (correct, and not correct with the fp8 control or a
planted fault), the scope reduction and the four new readers on a
hand-built trace and span ring with the numbers worked by hand, the
counts, the traffic file and the configuration file."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from chipbench import countsmla, scopes  # noqa: E402
from chipbench.harness import (Bench, Cell, load_module,  # noqa: E402
                               metric_reader, model_config)
from chipbench.peaks import peaks_for  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (BENCH / "configs" / "deepseek-v3-ep32-l7.json").read_text())
AS_RUN_KEYS = list(CONFIG["as_run"])


def as_run_of(cfg) -> dict:
    """The configuration file's `as_run` keys, read from a ModelConfig."""
    out = {}
    for key in AS_RUN_KEYS:
        v = cfg
        for part in key.split("."):
            v = getattr(v, part)
        out[key] = v
    return out


def tiny_cfg(**moe):
    """DeepSeek-V3's share at a tiny size: 16 experts in 4 groups (2 kept),
    top-2, 4 held from expert 4; 1 dense and 2 MoE layers."""
    from repro.configs import get_arch
    cfg = get_arch("deepseek-v3-671b-ep32").reduced(n_layers=3,
                                                     n_experts=16)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, **{"n_held": 4, "first_held": 4, **moe}))


TRAFFIC = {"driver": "decode_mla_moe", "shape": "decode_32k", "slots": 4,
           "max_len": 64, "check_requests": 3, "check_past": 28,
           "requests": [[5, 24], [9, 16], [7, 20], [3, 12]],
           "client_streams": [[2, 0], [1, 3], [0, 2], [3, 1]]}
# the program computes in float32 here (`run_tiny`), so a served token is
# the reference's best unless two logits lie within float32 rounding; at
# the preset's bfloat16 this tiny model (16 experts, top-2) flips routing
# on near-ties, and which requests the wall-clock window samples decides
# the reading
LIMIT = {"served_gap": 0.01}


def driver(cfg):
    drv = load_module(BENCH / "drivers" / "decode_mla_moe.py",
                      "t_decode_mla_moe")
    drv.model_config = lambda c: cfg
    return drv


@contextlib.contextmanager
def float32_compute():
    """The program's preset with float32 compute."""
    from repro.launch import presets
    orig = presets.preset_run
    presets.preset_run = lambda *a: orig(*a).with_(compute_dtype="float32")
    try:
        yield
    finally:
        presets.preset_run = orig


def run_tiny(cfg, seed=2 ** 32 + 7):
    """The driver on `cfg`, computing in float32, checked against the
    reference."""
    import jax
    drv = driver(cfg)
    cell = Cell("t.dsv3", 1, "tiny", {
        "as_run": as_run_of(cfg), "stored_bytes": {"param": 4, "kv": 4}},
        "tiny", TRAFFIC, LIMIT, SPEC)
    b = Bench(cell, seed, 0.5, False, time.monotonic(), jax.devices())
    b.watch.install()
    with float32_compute():
        return drv, b, drv.run(b)


@pytest.fixture(scope="module")
def dsv3_ok():
    return run_tiny(tiny_cfg())


def test_dsv3_cell_is_correct(dsv3_ok):
    _, b, out = dsv3_ok
    assert all(c.ok for c in out.checks), out.checks
    assert out.end_to_end["decode_tok_s"] > 0 and out.failed == 0
    lay = out.layer
    # every tick of the window carries the engine's routing counts: each
    # of the 4 slots routes top-2 in each of the 2 MoE layers, so at most
    # 16 pairs reach held experts, and at most 2 x 4 held experts are hit
    assert len(lay["held_routes"]) == lay["ticks"] > 0
    assert all(0 <= h <= 16 for h in lay["held_routes"])
    assert all(0 <= e <= 8 for e in lay["experts_hit"])
    assert sum(lay["held_routes"]) > 0
    assert lay["scope_s"] is None               # untraced
    # the check reaches a request longer than `check_past` positions
    assert max(len(r.prompt) + len(r.out_tokens)
               for r in out.kept["sample"]) > TRAFFIC["check_past"]


def test_dsv3_control_is_not_correct(dsv3_ok):
    """The reference one precision lower (fp8) in the program's place."""
    drv, b, out = dsv3_ok
    k = out.kept
    fp8 = drv.reference(b, b.cell.config["as_run"], k["sample"], ("fp8",))[0]
    gap = drv.served_gap(k["reference"], fp8[0].argmax(-1), k["valid"])
    assert gap.max() > LIMIT["served_gap"]


CONTROL = load_module(BENCH / "control_mla.py", "t_control_mla")


@pytest.mark.parametrize("fault", CONTROL.FAULTS)
def test_dsv3_planted_fault_is_not_correct(fault):
    """Each fault `control_mla.py` plants in the program (the softmax
    router in place of the sigmoid one; YaRN's mscale^2 dropped from the
    MLA softmax), against the reference of the published model."""
    from repro.models import attention, moe
    before = (moe.route, attention.mla_softmax_scale)
    with CONTROL.planted(fault):
        _, _, out = run_tiny(tiny_cfg())
    assert (moe.route, attention.mla_softmax_scale) == before
    assert not all(c.ok for c in out.checks), out.checks


def test_other_expert_sets_counts_tokens(dsv3_ok):
    drv, _, out = dsv3_ok
    chosen, valid = out.kept["chosen"][:1], out.kept["valid"]
    assert drv.other_expert_sets(np.concatenate([chosen, chosen]), valid) == 0
    assert valid[0, 0]
    # the last layer's set of the first checked token, with one expert
    # swapped for one it did not choose; the order within a set is moot
    other = chosen.copy()
    mine = set(chosen[0, -1, 0, 0].tolist())
    other[0, -1, 0, 0, 0] = min(set(range(16)) - mine)
    assert drv.other_expert_sets(np.concatenate([chosen, other]), valid) == 1
    assert drv.other_expert_sets(
        np.concatenate([chosen, chosen[..., ::-1]]), valid) == 0


def test_served_gap_takes_the_nearer_reference():
    """A token is scored by the reference that ranks it better: a token
    that one precision's routing explains is not held against the
    program, and one that neither explains is."""
    drv = driver(tiny_cfg())
    lg = np.array([[[[3.0, 1.0, 0.0], [0.0, 2.0, 1.5]]],     # float32
                   [[[2.0, 2.5, 0.0], [0.0, 1.0, 2.0]]]])    # bfloat16
    served = np.array([[1, 0]])
    np.testing.assert_allclose(drv.gaps(lg, served),
                               [[[2.0, 2.0]], [[0.0, 2.0]]])
    valid = np.array([[True, True]])
    np.testing.assert_allclose(drv.served_gap(lg, served, valid), [0.0, 2.0])
    np.testing.assert_allclose(
        drv.served_gap(lg, served, np.array([[False, True]])), [2.0])


# -- the program against the plain reference ---------------------------------


def test_engine_matches_the_reference_forward():
    """Prompts teacher-forced through `ServeEngine` and then decoded give,
    at every position a slot stepped, the logits of the reference's full
    forward pass over the same sequence. Both in float32 on the CPU; they
    differ by the order of summation (the program attends in the absorbed
    form over the latent cache, the reference in the expanded form),
    which stays under 1e-4 here, and both route every token alike."""
    import jax
    from chipbench import refmla, weights
    from repro.configs.base import RunConfig
    from repro.models.model import Model
    from repro.serve.engine import Request, ServeEngine
    cfg = tiny_cfg()
    model = Model(cfg, RunConfig(attn_impl="full", remat="nothing",
                                 compute_dtype="float32"))
    kd = weights.key_data(2 ** 33 + 3)
    params = refmla.make_params(kd, jax.eval_shape(
        model.init, jax.random.PRNGKey(0)), cfg.d_model)
    eng = ServeEngine(model, params, slots=2, max_len=32)
    seen = []
    step = eng._step

    def recording(p, b, c):
        out = step(p, b, c)
        seen.append(np.asarray(out[0][:, 0]))
        return out

    eng._step = recording
    prompts = [[3, 17, 5, 9, 200, 41], [7, 1, 250]]
    for rid, pr in enumerate(prompts):
        eng.submit(Request(rid, prompt=pr, max_new_tokens=6))
    done = sorted(eng.run(), key=lambda r: r.rid)
    # both requests are admitted on the first tick: slot i's row of tick t
    # is position t of its sequence
    seqs = [r.prompt + r.out_tokens[:-1] for r in done]
    tokens = np.zeros((2, 32), np.int32)
    pick = np.zeros((2, 11), np.int32)
    for i, sq in enumerate(seqs):
        tokens[i, :len(sq)] = sq
        pick[i, :len(sq)] = np.arange(len(sq))
    ref, _, _ = refmla.logits(kd, as_run_of(cfg), tokens, pick,
                              param_dtype="float32")
    ref = np.asarray(ref[0])
    for i, sq in enumerate(seqs):
        got = np.stack([seen[t][i, :cfg.vocab_size] for t in range(len(sq))])
        np.testing.assert_allclose(got, ref[i, :len(sq)], atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("first, tie",
                         [(0, 0.1), (2, 0.05), (6, 0.1), (4, np.inf)])
def test_reference_tie_margin_by_hand(first, tie):
    """Eight experts in four groups of two, two groups kept, top-2, two
    held from `first`. Scores 0.9 0.3 | 0.8 0.75 | 0.2 0.1 | 0.6 0.5:
    groups 1.2, 1.55, 0.3, 1.1 keep groups 1 and 0, and the experts 0 and
    2 are chosen. Held 0-1 or 6-7: the group edge (1.2 against 1.1) is
    theirs. Held 2-3: the expert edge (0.8 against 0.75) is. Held 4-5:
    neither."""
    import jax.numpy as jnp
    from chipbench import refmla
    s = np.array([0.9, 0.3, 0.8, 0.75, 0.2, 0.1, 0.6, 0.5])
    router = jnp.asarray(np.log(s / (1 - s))[None, :], jnp.float32)
    c = {"moe.n_group": 4, "moe.topk_group": 2, "moe.top_k": 2,
         "moe.first_held": first, "moe.n_held": 2, "moe.norm_topk_prob": True,
         "moe.routed_scaling_factor": 1.0}
    idx, gates, got = refmla.route(jnp.ones((1, 1)), router, jnp.zeros(8), c,
                                   "f32")
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    np.testing.assert_allclose(np.asarray(got), [tie], rtol=1e-5)


def test_widest_tie_reads_the_widest_gap():
    """The tie margin, least over references and layers, of the real token
    whose nearer-reference gap is the widest."""
    gaps = np.array([[[0.5, 0.1, 9.0]], [[0.2, 0.3, 9.0]]])   # (refs, 1, 3)
    valid = np.array([[True, True, False]])
    ties = np.array([[[[0.4, 0.01, 0.0]], [[0.02, 0.5, 0.0]]],
                     [[[0.3, 0.2, 0.0]], [[0.6, 0.05, 0.0]]]])
    # nearer gaps 0.2, 0.1 (the third is not real): the first token
    assert CONTROL.widest_tie(gaps, ties, valid) == pytest.approx(0.02)


def test_tick_past_runs_until_a_long_request_finishes():
    """After the window the engine ticks on until a request longer than
    `past` positions has finished, and no further; never past `most`."""
    drv = driver(tiny_cfg())
    done = []

    class Req:
        def __init__(self, n):
            self.prompt, self.out_tokens, self.done = [0] * n, [1], True

    class Loop:
        def tick(self, t_window):
            done.append(Req(10 * len(done)))

    eng = SimpleNamespace(finished=done)
    assert drv.tick_past(Loop(), eng, 25, 100) == 4
    assert drv.tick_past(Loop(), eng, 25, 100) == 0
    assert drv.tick_past(Loop(), eng, 1000, 3) == 3


def test_held_shares_sum_to_the_uncut_layer():
    """Four shares of 4 experts each (from 0, 4, 8 and 12), each run as one
    chip runs its share, add up, with the shared expert counted once, to
    the reference's layer holding all 16, and so does the uncut program
    layer. Float32; 1e-5 covers the order of summation."""
    import jax
    import jax.numpy as jnp
    from chipbench import refmla
    from repro.models import moe as M
    cfg = tiny_cfg()
    uncut = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=16, first_held=0))
    p = M.init_moe(jax.random.PRNGKey(5), uncut)
    p[M.BIAS] = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, cfg.d_model))
    h = x.reshape(-1, cfg.d_model)
    w = {"router": p["router"], M.BIAS: p[M.BIAS],
         **{f"shared/{k}": p["shared"][k] for k in ("gate", "up", "down")},
         **{k: p[k] for k in refmla.EXPERTS}}
    want, idx, _ = refmla.expert_layer(h, w, as_run_of(uncut), "f32")
    shared = refmla.swiglu(h, w["shared/gate"], w["shared/up"],
                           w["shared/down"], "f32")
    total = -3 * shared
    routed = []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_held=4, first_held=first))
        sp = dict(p, **{k: p[k][first:first + 4] for k in refmla.EXPERTS})
        out, _, counts = M.moe_dense(sp, x, share)
        total = total + out.reshape(h.shape)
        routed.append(np.asarray(counts))
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-5)
    full, _, counts = M.moe_dense(p, x, uncut)
    np.testing.assert_allclose(full.reshape(h.shape), want, atol=1e-5,
                               rtol=1e-5)
    # the shares' routing counts are the uncut layer's, split by share
    np.testing.assert_array_equal(np.concatenate(routed), np.asarray(counts))
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(idx).ravel(),
                                        minlength=16))
    assert int(jnp.sum(counts)) == 16 * cfg.moe.top_k


# -- the scope reduction and the readers ----------------------------------------

PATH = "jit(decode_step)/while/body/closed_call/{}/dot_general"
HLO = "\n".join([
    '  %fusion.1 = bf16[128,1536]{1,0} fusion(%p.1), kind=kOutput, '
    'calls=%fc.1, metadata={op_name="' + PATH.format("mla") + '" '
    'stack_frame_id=3}',
    '  %fusion.2 = f32[128,256]{1,0} fusion(%p.2), kind=kLoop, '
    'metadata={op_name="' + PATH.format("moe.route") + '"}',
    '  ROOT %fusion.3 = bf16[128,7168]{1,0} fusion(%p.3), '
    'metadata={op_name="' + PATH.format("moe.experts") + '"}',
    '  %add.4 = bf16[128,7168]{1,0} add(%a, %b), '
    'metadata={op_name="jit(decode_step)/while/body/add"}',
    '  %copy.5 = bf16[8]{0} copy(%c)',
    '  %ragged-dot-none.1 = bf16[1024,2048]{1,0} custom-call(%x), '
    'metadata={op_name="ragged-dot-none"}'])


def test_scope_of_reads_the_op_path():
    paths = scopes.op_paths(HLO)
    assert paths["fusion.1"] == PATH.format("mla")
    assert set(paths) == {"fusion.1", "fusion.2", "fusion.3", "add.4",
                          "ragged-dot-none.1"}
    op = "%{} = bf16[128,1536]{{1,0:T(8,128)}} fusion(bf16[...] %p), kind=k"
    assert [scopes.scope_of(op.format(n), paths) for n in (
        "fusion.1", "fusion.2", "fusion.3", "add.4", "copy.5",
        "ragged-dot-none.1", "ragged-dot-metadata")] == [
        "mla", "moe.route", "moe.experts", None, None, "moe.experts",
        "moe.experts"]


def scoped_trace():
    """A window of 1000 (10 ns units) and two runs of the step program,
    100-500 and 900-1100: an `mla` op that nests an unscoped one, ops of
    each scope, an op of another program that shares an instruction name
    (at 600), and an op past the window's end."""
    op = "%{} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p)"
    return {
        "/device:TPU:0": {
            "XLA Ops": [
                (op.format("fusion.1"), 100, 300), (op.format("add.4"), 150,
                                                    100),
                (op.format("fusion.2"), 400, 50),
                (op.format("ragged-dot-none.1"), 450, 40),
                (op.format("fusion.1"), 600, 100),
                (op.format("fusion.3"), 950, 100)],
            "XLA Modules": [("jit_decode_step(12)", 100, 400),
                            ("jit_other(3)", 600, 100),
                            ("jit_decode_step(12)", 900, 200)]},
        "/host:CPU": {"python": [("bench.window", 0, 1000)]},
    }


def test_scope_seconds_by_hand():
    tr = scopes.scoped(scoped_trace(), scopes.op_paths(HLO),
                       "jit_decode_step")
    got = scopes.seconds(tr)
    # mla: 300 - 100 nested; moe.experts: the ragged dot and the 50 of
    # fusion.3 inside the window; the other program's fusion.1 is none
    assert got == pytest.approx({"mla": 200e-9, "moe.route": 50e-9,
                                 "moe.experts": 90e-9})
    none = scopes.scoped(scoped_trace(), {}, "jit_decode_step")
    assert scopes.seconds(none) == pytest.approx(
        {"mla": 0.0, "moe.route": 0.0, "moe.experts": 40e-9})
    assert scopes.seconds(scopes.scoped(scoped_trace(), {}, "jit_x")) is None
    assert scopes.scope_seconds(None, HLO) is None


def test_step_text_carries_the_scopes():
    """The program names its scopes: the engine step's compiled text holds
    ops under `mla`, `moe.route` and `moe.experts`."""
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine
    import jax
    from repro.configs.base import RunConfig
    cfg = tiny_cfg()
    model = Model(cfg, RunConfig(attn_impl="full", remat="nothing"))
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), slots=2,
                      max_len=16)
    drv = driver(cfg)
    found = {s for p in scopes.op_paths(drv.step_text(eng)).values()
             for s in scopes.SCOPES if s in p.split("/")}
    assert found == set(scopes.SCOPES)


def reader(name):
    return load_module(metric_reader(name), f"t_{name.replace('.', '_')}")


C = CONFIG["as_run"]
V5E = peaks_for("TPU v5 lite")


def window(scope_s, routes=((40, 8), (32, 7)), slots=(128, 128),
           kv=(70000, 70200), window_s=0.1):
    lay = {"config": C, "slot_ticks": sum(slots), "tick_slots": list(slots),
           "tick_kv": list(kv), "kv_positions": sum(kv), "ticks": len(kv),
           "held_routes": [r[0] for r in routes],
           "experts_hit": [r[1] for r in routes], "scope_s": scope_s,
           "param_itemsize": 2, "kv_itemsize": 2}
    b = SimpleNamespace(window_s=window_s, devices=[None], peaks=V5E)
    return b, SimpleNamespace(layer=lay)


def test_attn_roofline_by_hand():
    b, out = window({"mla": 0.02, "moe.route": 0.001, "moe.experts": 0.004})
    # one tick: 7 layers x 187,105,280 weights (+2,048 norm scales) x 2 B,
    # and (70,000 + 128) positions x 576 values x 2 B
    w = 187_105_280
    assert countsmla.mla_weights(C) == w
    bytes0 = 7 * ((w + 2048) * 2 + (70000 + 128) * 576 * 2)
    flops0 = 2 * 7 * (128 * w + 128 * 1088 * 70000)
    assert countsmla.attn_bytes(C, 128, 70000, 2, 2) == bytes0
    assert countsmla.attn_flops(C, 128, 70000) == flops0
    # bandwidth binds: 3.19 GB at 819 GB/s against 0.48 TFLOP at 197
    t0 = max(flops0 / 197e12, bytes0 / 819e9)
    assert t0 == bytes0 / 819e9
    t1 = countsmla.attn_bytes(C, 128, 70200, 2, 2) / 819e9
    got = reader("attn_roofline.dsv3").read(b, out)
    assert got == pytest.approx(100 * (t0 + t1) / 0.02)


def test_experts_roofline_by_hand():
    b, out = window({"mla": 0.02, "moe.route": 0.001, "moe.experts": 0.004})
    e = 3 * 7168 * 2048
    fixed = 7168 * 256 + e
    assert countsmla.experts_flops(C, 128, 40) == \
        2 * (4 * 128 * fixed + 40 * e)
    assert countsmla.experts_bytes(C, 8, 2) == 2 * (4 * (fixed + 256) + 8 * e)
    need = sum(countsmla.experts_bytes(C, hit, 2) / 819e9 for hit in (8, 7))
    got = reader("experts_roofline.dsv3").read(b, out)
    assert got == pytest.approx(100 * need / 0.005)


def test_mfu_by_hand():
    b, out = window(None)
    per_token = (7 * countsmla.mla_weights(C) + 3 * 3 * 7168 * 18432 +
                 4 * (7168 * 256 + 3 * 7168 * 2048) + 16160 * 7168)
    flops = 2 * (256 * per_token + 7 * 128 * 1088 * 140200 +
                 72 * 3 * 7168 * 2048)
    assert countsmla.step_flops(C, 256, 140200, 72) == flops
    got = reader("mfu.dsv3").read(b, out)
    assert got == pytest.approx(100 * flops / 0.1 / 197e12)


def test_hbm_bw_share_by_hand():
    """Two runs of the step program of 10 ms each in a 50 ms window, and a
    run of another program: the bytes of the window's mean tick, twice,
    over 20 ms, over 819 GB/s."""
    b, out = window(None)
    ms = 1_000_000
    trace = {"/device:TPU:0": {"XLA Modules": [
        ("jit_decode_step(12)", 1 * ms, 10 * ms),
        ("jit_decode_step(12)", 20 * ms, 10 * ms),
        ("jit_other(3)", 40 * ms, 1 * ms)]},
        "/host:CPU": {"python": [("bench.window", 0, 50 * ms)]}}
    b.load_trace = lambda: trace
    # the tick of 70,000 positions and 8 held experts hit: the MLA and the
    # expert layers as counted above, 3 dense MLPs, the head over 16,160
    # rows, 15 norm scales and 128 embedding rows of 7,168
    rest = 3 * 3 * 7168 * 18432 + 16160 * 7168 + 15 * 7168 + 128 * 7168
    tick0 = countsmla.attn_bytes(C, 128, 70000, 2, 2) + \
        countsmla.experts_bytes(C, 8, 2) + 2 * rest
    assert countsmla.step_bytes(C, 128, 70000, 8, 2, 2) == tick0
    tick1 = countsmla.step_bytes(C, 128, 70200, 7, 2, 2)
    got = reader("hbm_bw_share.dsv3").read(b, out)
    assert got == pytest.approx(100 * (tick0 + tick1) / 2 * 2 / 0.02 / 819e9)


def test_readers_read_nothing_without_routes_or_scopes():
    """A program whose ticks carry no routing attributes (as before this
    cell's program), or an untraced window."""
    b, out = window({"mla": 0.02}, routes=())
    b.load_trace = lambda: {"/device:TPU:0": {"XLA Modules": [
        ("jit_decode_step(1)", 0, 10)]}}
    assert reader("mfu.dsv3").read(b, out) is None
    assert reader("experts_roofline.dsv3").read(b, out) is None
    assert reader("hbm_bw_share.dsv3").read(b, out) is None
    b, out = window(None)
    b.load_trace = lambda: None
    assert reader("attn_roofline.dsv3").read(b, out) is None
    assert reader("experts_roofline.dsv3").read(b, out) is None
    assert reader("hbm_bw_share.dsv3").read(b, out) is None


# -- the files -----------------------------------------------------------------


def test_traffic_file_staggers_128_clients():
    tr = json.loads((BENCH / "traffic" / "chat-closed128.json").read_text())
    assert tr["slots"] == len(tr["client_streams"]) == 128
    assert tr["requests"] == json.loads(
        (BENCH / "traffic" / "chat-closed4.json").read_text())["requests"]
    starts = [s[0] for s in tr["client_streams"]]
    assert sorted(starts) == sorted(list(range(16)) * 8)
    # each client cycles 4 entries, one from every quarter of the table
    for s in tr["client_streams"]:
        assert sorted(i // 4 for i in s) == [0, 1, 2, 3]


def test_configuration_file_is_the_registered_share():
    cfg = model_config(CONFIG)
    assert (cfg.d_model, cfg.n_heads, cfg.mla.q_lora_rank,
            cfg.mla.kv_lora_rank, cfg.mla.qk_rope_dim, cfg.mla.v_head_dim,
            cfg.moe.d_ff_dense, cfg.moe.d_ff_expert, cfg.moe.n_experts,
            cfg.moe.top_k, cfg.moe.n_group, cfg.moe.topk_group,
            cfg.moe.n_shared_experts) == (
        7168, 128, 1536, 512, 64, 128, 18432, 2048, 256, 8, 8, 4, 1)
    assert (cfg.n_layers, cfg.moe.held, cfg.vocab_size) == (7, 8, 16160)
    assert CONFIG["n_routed_experts"] == 8
    assert CONFIG["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    (c,) = [c for c in SPEC["configs"] if c["name"] == "deepseek-v3-ep32-l7"]
    assert sorted(c["reduced"]) == sorted(CONFIG["changed"])
