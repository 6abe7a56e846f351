"""Serving window of DeepSeek-V3's chip share (MLA attention, the
held-experts layer): decode.py's engine, clients and loop, checked
against the plain reference `chipbench/refmla.py`.

`client_streams`, `Loop` and `check_sample` are decode.py's, by import,
and the window runs as decode.py's does. Four things differ. The
weights are made by `refmla.make_params`, whose rule the reference
shares (decode.py's `weights.make_params` zeroes every matrix named
`gate` and draws the MLA's norm scales). The sampled requests are
checked against `refmla.logits` in float32 and at the program's
bfloat16, and a served token's gap is the smaller of the two: where
the router's top-k is a near-tie, the two precisions may choose other
experts, and the program may side with either. Where the traffic file
sets `check_past`, the engine ticks on after the window, outside every
measurement, until a request longer than that many positions has
finished, so that the check reaches positions the window alone does
not. And the window keeps, for every tick,
the engine's routing attributes from its `engine.tick` span
(`held_routes`, `experts_hit`) and the driver's own slots and filled
positions, and, when traced, the device seconds of the step's `mla`,
`moe.route` and `moe.experts` scopes (`chipbench/scopes.py`), read from
the trace file and the step's compiled text before the harness's
reduction, which keeps no scope.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench import refmla, ring, scopes
from chipbench.harness import BENCH, Check, Outcome, load_module, model_config

_decode = load_module(BENCH / "drivers" / "decode.py",
                      "chipbench_driver_decode")
client_streams = _decode.client_streams
Loop = _decode.Loop
check_sample = _decode.check_sample


def build(bench):
    """The engine over seeded weights (refmla's rule), on the program's
    own knobs."""
    import jax
    from repro.configs import SHAPES
    from repro.launch.mesh import make_mesh, mesh_config
    from repro.launch.presets import preset_run
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    tr = bench.cell.traffic
    cfg = model_config(bench.cell.config)
    shape = dataclasses.replace(SHAPES[tr["shape"]], seq_len=tr["max_len"],
                                global_batch=tr["slots"])
    mesh = make_mesh((1, 1), ("data", "model"), devices=bench.devices[:1])
    model = Model(cfg, preset_run(cfg, shape, mesh_config(mesh)))
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = refmla.make_params(bench.key, abstract, cfg.d_model)
    engine = ServeEngine(model, params, tr["slots"], tr["max_len"],
                         seed=bench.seed % (1 << 31))
    return cfg, engine


def step_text(engine) -> str:
    """The compiled HLO text of the engine's step program, whose
    instructions the trace's device ops are: compiled again on the same
    arguments, which gives the same program (from the compile cache)."""
    return engine._step.lower(engine.params, engine._batch(),
                              engine.caches).compile().as_text()


# the references a served token is scored against: float32, and the
# program's own precision (bfloat16 operands and residual stream)
SOUND = ("f32", "bf16")


def reference(bench, c, sample, modes=SOUND):
    """The reference in each of `modes` over the sampled requests: logits
    at every position that served a token (modes, n, P, V), the experts
    each MoE layer chose and how near each choice came to a tie
    (`refmla.logits`), the served tokens (n, P) and which are real."""
    max_len = bench.cell.traffic["max_len"]
    n = len(sample)
    P = max(len(r.out_tokens) for r in sample)
    tokens = np.zeros((n, max_len), np.int32)
    pick = np.zeros((n, P), np.int32)
    served = np.zeros((n, P), np.int32)
    valid = np.zeros((n, P), bool)
    for i, r in enumerate(sample):
        seq = list(r.prompt) + list(r.out_tokens[:-1])
        tokens[i, :len(seq)] = seq
        m = len(r.out_tokens)
        pos = len(r.prompt) - 1 + np.arange(m)
        pick[i, :m], pick[i, m:] = pos, pos[-1]
        served[i, :m] = r.out_tokens
        valid[i, :m] = True
    itemsize = bench.cell.config["stored_bytes"]["param"]
    lg, chosen, ties = refmla.logits(
        bench.key, c, tokens, pick, modes,
        param_dtype={2: "bfloat16", 4: "float32"}[itemsize])
    return (np.asarray(lg), np.asarray(chosen), np.asarray(ties), served,
            valid)


def gaps(lg, tokens) -> np.ndarray:
    """Per reference of `lg` (refs, n, P, V): its best logit minus its
    logit of `tokens` (n, P) -> (refs, n, P)."""
    got = np.take_along_axis(lg, np.broadcast_to(
        tokens, lg.shape[:-1])[..., None], axis=-1)[..., 0]
    return lg.max(axis=-1) - got


def served_gap(lg, tokens, valid) -> np.ndarray:
    """Each real token's gap: the least over the references, so that a
    token that one precision's routing of a near-tie explains is not held
    against the program."""
    return gaps(lg, tokens).min(axis=0)[valid]


def other_expert_sets(chosen, valid) -> int:
    """Checked tokens whose chosen expert set at some MoE layer differs
    between the first two references (`reference`'s `chosen`)."""
    differ = (np.sort(chosen[0], -1) != np.sort(chosen[1], -1)).any(-1)
    return int((differ.any(0) & valid).sum())


def tick_past(loop, engine, past: int, most: int) -> int:
    """Ticks, outside the window, until a request longer than `past`
    positions has finished (at most `most` ticks); returns the ticks."""
    n = 0
    while n < most and not any(len(r.prompt) + len(r.out_tokens) > past
                               for r in engine.finished if r.done):
        loop.tick(t_window=float("inf"))
        n += 1
    return n


def run(bench):
    tr = bench.cell.traffic
    cfg, engine = build(bench)
    loop = Loop(engine, client_streams(bench, cfg.vocab_size))
    loop.tick(t_window=float("inf"))        # admits and compiles: set-up

    ticks = []                      # (seconds, slots, positions, most)
    itl, tokens, t_prev = [], 0, None
    with bench.window():
        t_stop = bench.t_start + bench.seconds
        t_prev = bench.t_start
        while t_prev < t_stop:
            t, slots, kv, kv_max, new, g = loop.tick(bench.t_start)
            ticks.append((t - t_prev, slots, kv, kv_max))
            tokens += new
            itl += g
            t_prev = t
        bench.end_window(t_prev)
    bench.read_memory_peak()
    spans = ring.window_spans(bench, "engine.tick") or []
    routed = [(s.attrs["held_routes"], s.attrs["experts_hit"])
              for s in spans if "held_routes" in s.attrs]
    slowest = {c.name: round(1e3 * c.wall_s, 1) for c in max(
        spans, key=lambda s: s.wall_s).children} if spans else None
    if len(routed) != len(ticks):
        routed = []
    scope_s = scopes.scope_seconds(bench.trace_dir, step_text(engine)) \
        if bench.trace else None
    after = tick_past(loop, engine, tr["check_past"], 2 * tr["max_len"]) \
        if "check_past" in tr else 0

    attempted = len(loop.fed)
    finished = [r for r in engine.finished if r.done]
    bad = [r for r in finished
           if len(r.out_tokens) != r.max_new_tokens or
           not all(0 <= t < cfg.vocab_size for t in r.out_tokens)]
    sample = check_sample(bench, [r for r in finished if r not in bad],
                          tr["check_requests"])
    run_cfg = engine.model.run
    del engine, loop
    gc.collect()

    c = bench.cell.config["as_run"]
    t_ref = time.monotonic()
    ref, chosen, ties, served, valid = reference(bench, c, sample)
    t_ref = time.monotonic() - t_ref
    gap = served_gap(ref, served, valid)
    reach = max(len(r.prompt) + len(r.out_tokens) for r in sample) - 1
    checks = [Check("served_gap", float(gap.max()),
                    bench.cell.limits["served_gap"])]
    itl = np.asarray(itl)
    tick_s = [t[0] for t in ticks]
    slot_ticks = sum(t[1] for t in ticks)
    kv_mean = sum(t[2] for t in ticks) / max(slot_ticks, 1)
    held = sum(r[0] for r in routed)
    return Outcome(
        attempted=attempted, failed=len(bad),
        end_to_end={"decode_tok_s": tokens / bench.window_s,
                    "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3},
        checks=checks,
        layer={"tick_s": tick_s,
               "slot_ticks": slot_ticks,
               "kv_positions": sum(t[2] for t in ticks),
               "ticks": len(ticks), "config": c,
               "param_itemsize": np.dtype(run_cfg.param_dtype).itemsize,
               "kv_itemsize": np.dtype(run_cfg.compute_dtype).itemsize,
               "tick_slots": [t[1] for t in ticks],
               "tick_kv": [t[2] for t in ticks],
               "held_routes": [r[0] for r in routed],
               "experts_hit": [r[1] for r in routed],
               "scope_s": scope_s},
        kept={"sample": sample, "reference": ref, "chosen": chosen,
              "ties": ties, "served": served, "valid": valid},
        notes=[f"decode: {len(ticks)} ticks, {tokens} output tokens, "
               f"{len(itl)} gaps, {len(finished)} finished; checked "
               f"{len(sample)} requests, {int(gap.size)} served tokens "
               f"up to position {reach}, "
               f"after {after} ticks past the window; expert sets of the "
               f"references differ for {other_expert_sets(chosen, valid)} "
               f"(reference {t_ref:.1f}s)",
               f"decode: ticks median {1e3 * np.median(tick_s):.2f} ms, "
               f"slowest {[round(1e3 * t, 1) for t in sorted(tick_s)[-3:]]}"
               f" ms; the slowest tick's spans {slowest}",
               f"decode: served {slot_ticks} slot-ticks, "
               f"{tokens / max(slot_ticks, 1):.4f} of them output; filled "
               f"positions mean {kv_mean:.1f}, most "
               f"{max((t[3] for t in ticks), default=0)}",
               f"routing: {held} pairs to held experts over "
               f"{len(routed)} ticks ({held / max(len(routed), 1):.1f} a "
               f"tick), held experts hit "
               f"{sum(r[1] for r in routed) / max(len(routed), 1):.2f} a "
               f"tick; scopes {scope_s}",
               f"memory: peak {bench.memory_peak} B"])
