"""Serving engine: continuous batching, slot reuse, sampling."""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import RunConfig
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine
from repro.telemetry import TraceRing
from repro.telemetry import spans as spans_mod

RUN = RunConfig(attn_impl="full", remat="nothing", compute_dtype="float32")
# parameters stored in float32, computed in bfloat16: the serving copy
RUN_BF16 = RunConfig(attn_impl="full", remat="nothing",
                     compute_dtype="bfloat16")


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("deepseek-7b").reduced()
    m = build_model(cfg, RUN)
    return m, m.init(jax.random.PRNGKey(0))


def test_engine_serves_all_requests(small_model):
    m, p = small_model
    eng = ServeEngine(m, p, slots=2, max_len=32)
    for rid in range(5):
        eng.submit(Request(rid, prompt=[rid + 1, 2, 3], max_new_tokens=4))
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.out_tokens) == 4 for r in done)


def test_slot_reuse_matches_fresh_engine(small_model):
    """A request served in a recycled slot produces the same tokens as on a
    fresh engine — stale cache state is fully isolated."""
    m, p = small_model
    eng = ServeEngine(m, p, slots=1, max_len=32)
    eng.submit(Request(0, prompt=[9, 8, 7], max_new_tokens=5))
    eng.submit(Request(1, prompt=[3, 2, 1], max_new_tokens=5))
    done = eng.run()
    r1 = [r for r in done if r.rid == 1][0]

    fresh = ServeEngine(m, p, slots=1, max_len=32)
    fresh.submit(Request(1, prompt=[3, 2, 1], max_new_tokens=5))
    d2 = fresh.run()
    assert r1.out_tokens == d2[0].out_tokens


def test_greedy_matches_forward_argmax(small_model):
    """Engine greedy decode == argmax over model.forward logits chain."""
    m, p = small_model
    prompt = [5, 11, 2]
    eng = ServeEngine(m, p, slots=1, max_len=32)
    eng.submit(Request(0, prompt=prompt, max_new_tokens=3))
    out = eng.run()[0].out_tokens

    toks = list(prompt)
    for _ in range(3):
        lg, _ = m.forward(p, {"tokens": jnp.asarray([toks])})
        toks.append(int(jnp.argmax(lg[0, -1])))
    assert out == toks[len(prompt):]


def test_ssm_engine(small_model):
    """Attention-free arch serves through the same engine (state caches)."""
    cfg = get_arch("rwkv6-7b").reduced()
    m = build_model(cfg, RUN)
    p = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, p, slots=2, max_len=16)
    for rid in range(3):
        eng.submit(Request(rid, prompt=[rid + 1, 4], max_new_tokens=3))
    done = eng.run()
    assert len(done) == 3 and all(len(r.out_tokens) == 3 for r in done)


def _f32_step(m):
    return jax.jit(lambda p, b, c: m.decode_step(p, b, c, None))


def _same(a, b) -> bool:
    return all(x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                     np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("arch", ["deepseek-7b", "olmoe-1b-7b", "rwkv6-7b",
                                  "zamba2-7b"])
def test_serving_copy_matches_f32_step(arch):
    """The engine's step on its bf16 serving copy and the model's step on
    the f32 tree, on the same tokens and caches, give bitwise equal
    logits and caches, and the engine serves the f32 step's greedy
    tokens."""
    m = build_model(get_arch(arch).reduced(), RUN_BF16)
    p = m.init(jax.random.PRNGKey(1))
    prompts = [[5, 11, 2], [7, 3, 9]]
    eng = ServeEngine(m, p, slots=2, max_len=16)
    assert any(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(eng.params))

    f32 = _f32_step(m)
    caches = m.init_caches(2, 16)
    tok = np.asarray([pr[0] for pr in prompts], np.int32)
    chain = [[], []]
    for t in range(1, len(prompts[0]) + 4):
        batch = {"tokens": jnp.asarray(tok)[:, None]}
        lg, new = f32(p, batch, caches)
        lg_s, new_s = eng._step(eng.params, batch, caches)[:2]
        assert _same(lg, lg_s) and _same(new, new_s), t
        caches = new
        if t < len(prompts[0]):
            tok = np.asarray([pr[t] for pr in prompts], np.int32)
        else:
            tok = np.argmax(np.asarray(lg[:, 0]), axis=-1).astype(np.int32)
            for i in range(2):
                chain[i].append(int(tok[i]))

    for rid, pr in enumerate(prompts):
        eng.submit(Request(rid, prompt=pr, max_new_tokens=4))
    done = sorted(eng.run(), key=lambda r: r.rid)
    assert [r.out_tokens for r in done] == chain


@pytest.mark.parametrize("arch", ["deepseek-7b", "deepseek-v3-671b-ep32",
                                  "rwkv6-7b"])
def test_a_reused_slot_serves_as_a_fresh_one(arch):
    """A request admitted to a slot that served another before it gets
    the tokens it gets alone: the slot's position and recurrent state
    are reset, and its stale keys and values are never attended."""
    m = build_model(get_arch(arch).reduced(), RUN_BF16)
    p = m.init(jax.random.PRNGKey(3))
    eng = ServeEngine(m, p, slots=1, max_len=32)
    eng.submit(Request(0, prompt=[9, 4, 17, 2, 30, 8], max_new_tokens=7))
    eng.submit(Request(1, prompt=[5, 11], max_new_tokens=5))
    reused = eng.run()[1]
    fresh = ServeEngine(m, p, slots=1, max_len=32)
    fresh.submit(Request(1, prompt=[5, 11], max_new_tokens=5))
    assert reused.out_tokens == fresh.run()[0].out_tokens


def test_prepare_casts_matmul_weights_only(monkeypatch):
    """On the dense model, `engine.prepare` reports the embedding and the
    norm scales kept, as the caller's arrays, and every matmul weight
    cast: the f32 weight rounded to bf16."""
    ring = TraceRing(64)
    monkeypatch.setattr(spans_mod, "_default_ring", ring)
    m = build_model(get_arch("deepseek-7b").reduced(), RUN_BF16)
    p = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, p, slots=1, max_len=16)

    named = {jax.tree_util.keystr(k): a
             for k, a in jax.tree_util.tree_flatten_with_path(p)[0]}
    served = {jax.tree_util.keystr(k): a for k, a in
              jax.tree_util.tree_flatten_with_path(eng.params)[0]}
    kept = {k for k, a in served.items() if a is named[k]}
    cast = set(served) - kept
    assert kept == {"['embed']", "['norm']", "['layers']['ln1']",
                    "['layers']['ln2']"}
    assert {k.rsplit("[", 1)[-1].strip("']") for k in cast} == \
        {"wq", "wk", "wv", "wo", "gate", "up", "down", "head"}
    for k in cast:
        assert served[k].dtype == jnp.bfloat16
        assert _same(served[k], named[k].astype(jnp.bfloat16))

    (prep,) = [r for r in ring.traces() if r.name == "engine.prepare"]
    assert prep.attrs["cast_leaves"] == 8
    assert prep.attrs["kept_leaves"] == 4
    assert prep.attrs["cast_bytes"] == \
        sum(2 * named[k].size for k in cast)
    assert prep.wall_s > 0


def test_engine_holds_no_float32_copy_of_cast_leaves():
    """Once the caller lets go of its tree, the float32 form of every cast
    leaf is freed: the engine holds the serving form alone, and the
    leaves it keeps are the caller's arrays."""
    m = build_model(get_arch("deepseek-7b").reduced(), RUN_BF16)
    p = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, p, slots=1, max_len=16)
    pairs = list(zip(jax.tree.leaves(p), jax.tree.leaves(eng.params)))
    cast = [weakref.ref(a) for a, s in pairs if s is not a]
    kept = [weakref.ref(a) for a, s in pairs if s is a]
    assert len(cast) == 8 and len(kept) == 4
    del p, pairs
    gc.collect()
    assert all(r() is None for r in cast)
    assert all(r() is not None for r in kept)
    eng.submit(Request(0, prompt=[5, 11, 2], max_new_tokens=3))
    assert len(eng.run()[0].out_tokens) == 3


def test_assigning_params_rederives_the_serving_copy():
    """Swapping the weights can never serve stale ones: the served logits
    follow the new tree, bitwise as its f32 step gives them."""
    m = build_model(get_arch("deepseek-7b").reduced(), RUN_BF16)
    p, p2 = m.init(jax.random.PRNGKey(0)), m.init(jax.random.PRNGKey(2))
    eng = ServeEngine(m, p, slots=1, max_len=16)
    batch = {"tokens": jnp.asarray([[5]], jnp.int32)}
    caches = m.init_caches(1, 16)
    f32 = _f32_step(m)
    before = eng._step(eng.params, batch, caches)[0]
    assert _same(before, f32(p, batch, caches)[0])

    eng.params = p2
    after = eng._step(eng.params, batch, caches)[0]
    assert _same(after, f32(p2, batch, caches)[0])
    assert not _same(after, before)

    eng.submit(Request(0, prompt=[5, 11, 2], max_new_tokens=3))
    fresh = ServeEngine(m, p2, slots=1, max_len=16)
    fresh.submit(Request(0, prompt=[5, 11, 2], max_new_tokens=3))
    assert eng.run()[0].out_tokens == fresh.run()[0].out_tokens
