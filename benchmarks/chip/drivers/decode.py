"""Serving window: closed-loop clients driving `ServeEngine.submit` and
`tick` on the configuration's model.

Set-up makes the weights on the device from the seed, builds the engine
with the program's own RunConfig (`preset_run`), submits each client's
first request and ticks once, which compiles every program the window
runs. The window then ticks for `--seconds`; each client submits its next
request when the last one finishes. Each client cycles through its own
list of entries of the traffic file's (prompt, output) table, starting
where the file says, so the clients open the window at different lengths
and the window serves the table's mix; every seed carries the same load,
and the seed draws the token ids, per client. Once the window has closed,
a sample of the finished requests, the longest among them, is run through
the plain reference, and the widest gap by which a served token's logit
lies below the reference's best is compared with its limit.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import time

import numpy as np

from chipbench import refmodels, tracing
from chipbench.harness import Check, Outcome, model_config
from chipbench.weights import make_params


def client_streams(bench, vocab: int):
    """One endless stream of requests per client: the entries of the
    traffic file's table that `client_streams` lists for it, over and
    over, token ids drawn from the seed and the client."""
    from repro.serve.engine import Request
    tr = bench.cell.traffic
    table = tr["requests"]
    rid = iter(range(1 << 62))

    def stream(c, entries):
        rng = np.random.default_rng([bench.seed, c + 1])
        for i in itertools.cycle(entries):
            p, o = table[i]
            yield Request(rid=next(rid), max_new_tokens=o,
                          prompt=rng.integers(0, vocab, p).tolist())

    return [stream(c, e) for c, e in enumerate(tr["client_streams"])]


def build(bench):
    """The engine over seeded weights, on the program's own knobs."""
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
    params = make_params(bench.key, abstract, cfg.d_model)
    engine = ServeEngine(model, params, tr["slots"], tr["max_len"],
                         seed=bench.seed % (1 << 31))
    return cfg, engine


class Loop:
    """The clients and the bookkeeping of what each tick produced."""

    def __init__(self, engine, streams):
        self.engine = engine
        self.streams = streams
        self.client_of = {}
        self.fed = {}                 # rid -> tokens fed so far
        self.seen = {}                # rid -> output tokens seen
        self.last_t = {}              # rid -> time of its last token
        self.n_finished = 0
        for c in range(len(streams)):
            self.submit(c)

    def submit(self, client: int):
        r = next(self.streams[client])
        self.client_of[r.rid] = client
        self.engine.submit(r)

    def tick(self, t_window: float):
        """One engine tick. Returns (end time, slots stepped, filled
        positions summed over them, the most filled, new output tokens,
        inter-token gaps that lie inside the window)."""
        eng = self.engine
        before = [r for r in eng.active if r is not None]
        with tracing.annotate("bench.tick"):
            eng.tick()
        t = time.monotonic()
        newly = eng.finished[self.n_finished:]
        self.n_finished = len(eng.finished)
        stepped = {r.rid: r for r in before}
        for r in list(eng.active) + list(newly):
            if r is not None:
                stepped.setdefault(r.rid, r)
        kv, kv_max, new, gaps = 0, 0, 0, []
        for rid, r in stepped.items():
            pos = self.fed.get(rid, 0)
            self.fed[rid] = pos + 1
            kv += pos + 1
            kv_max = max(kv_max, pos + 1)
            n_out = len(r.out_tokens)
            if n_out > self.seen.get(rid, 0):
                self.seen[rid] = n_out
                prev = self.last_t.get(rid)
                if prev is not None and prev >= t_window:
                    gaps.append(t - prev)
                self.last_t[rid] = t
                new += 1
        for r in newly:
            self.submit(self.client_of[r.rid])
        return t, len(stepped), kv, kv_max, new, gaps


def check_sample(bench, finished, k: int):
    """k finished requests drawn from the seed, the longest among them."""
    order = sorted(finished, key=lambda r: (len(r.prompt) + len(r.out_tokens),
                                            r.rid))
    longest = order[-1]
    rest = order[:-1]
    rng = np.random.default_rng([bench.seed, 0])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(bench, c, sample, mode: str = "f32", against=None):
    """Per served token: the reference's best logit minus its logit of the
    served token. With `against` (the float32 logits), the gap of the token
    that `mode` ranks first instead: the control."""
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
    lg = np.asarray(refmodels.dense_logits(bench.key, c, tokens, pick, mode))
    if against is not None:
        served = np.argmax(lg, axis=-1)
        lg = against
    best = lg.max(axis=-1)
    got = np.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    return (best - got)[valid], lg


def run(bench):
    tr = bench.cell.traffic
    cfg, engine = build(bench)
    loop = Loop(engine, client_streams(bench, cfg.vocab_size))
    loop.tick(t_window=float("inf"))        # admits and compiles: set-up

    ticks = []                      # (seconds, slots, positions, most)
    gaps, tokens, t_prev = [], 0, None
    with bench.window():
        t_stop = bench.t_start + bench.seconds
        t_prev = bench.t_start
        while t_prev < t_stop:
            t, slots, kv, kv_max, new, g = loop.tick(bench.t_start)
            ticks.append((t - t_prev, slots, kv, kv_max))
            tokens += new
            gaps += g
            t_prev = t
        bench.end_window(t_prev)
    bench.read_memory_peak()

    attempted = len({rid for rid, n in loop.fed.items()})
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
    gap, ref = served_gaps(bench, c, sample)
    checks = [Check("served_gap", float(gap.max()),
                    bench.cell.limits["served_gap"])]
    itl = np.asarray(gaps)
    slot_ticks = sum(t[1] for t in ticks)
    kv_mean = sum(t[2] for t in ticks) / max(slot_ticks, 1)
    kv_most = max((t[3] for t in ticks), default=0)
    served = sorted((len(r.prompt), len(r.out_tokens)) for r in finished)
    return Outcome(
        attempted=attempted, failed=len(bad),
        end_to_end={"decode_tok_s": tokens / bench.window_s,
                    "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3},
        checks=checks,
        layer={"tick_s": [t[0] for t in ticks],
               "slot_ticks": slot_ticks,
               "kv_positions": sum(t[2] for t in ticks),
               "ticks": len(ticks), "config": c,
               "param_itemsize": np.dtype(run_cfg.param_dtype).itemsize,
               "kv_itemsize": np.dtype(run_cfg.compute_dtype).itemsize},
        kept={"sample": sample, "reference": ref},
        notes=[f"decode: {len(ticks)} ticks, {tokens} output tokens, "
               f"{len(itl)} gaps, {len(finished)} finished; checked "
               f"{len(sample)} requests, {int(gap.size)} served tokens",
               f"decode: served {slot_ticks} slot-ticks, "
               f"{tokens / max(slot_ticks, 1):.4f} of them output; filled "
               f"positions mean {kv_mean:.1f}, most {kv_most}; finished "
               f"(prompt, output) {served}"])
