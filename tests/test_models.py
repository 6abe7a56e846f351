"""Per-architecture smoke tests (reduced configs, CPU) + numerical
equivalence of the optimized attention/SSM paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import RunConfig
from repro.models import attention as A
from repro.models import build_model, analytic_param_count
from repro.models.rwkv import wkv_chunked, wkv_recurrent
from repro.models.ssm import ssd_chunked, ssd_recurrent

KEY = jax.random.PRNGKey(0)
RUN32 = RunConfig(attn_impl="full", remat="nothing", compute_dtype="float32")


def batch_for(cfg, tokens):
    b = {"tokens": tokens, "labels": tokens}
    B = tokens.shape[0]
    if cfg.family == "vlm":
        b["media"] = 0.1 * jnp.ones(
            (B, cfg.cross_attn.n_media_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        b["frames"] = 0.1 * jnp.ones(
            (B, cfg.encdec.enc_len, cfg.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_smoke_forward_and_train_step(arch):
    """One forward + one SGD-free train step on a reduced config: output
    shapes correct, loss finite, grads finite."""
    cfg = ARCHS[arch].reduced()
    m = build_model(cfg, RUN32)
    params = m.init(KEY)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = batch_for(cfg, toks)
    logits, _ = m.forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, grads = jax.value_and_grad(
        lambda p: m.loss_fn(p, batch)[0])(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_decode_consistency(arch):
    """Token-by-token decode reproduces the full forward logits (f32)."""
    cfg = ARCHS[arch].reduced()
    m = build_model(cfg, RUN32)
    params = m.init(KEY)
    B, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    fb = batch_for(cfg, toks)
    lg_full, _ = m.forward(params, fb)
    caches = m.init_caches(B, S)
    lgs = []
    for t in range(S):
        db = batch_for(cfg, toks[:, t:t + 1])
        db.pop("labels")
        if cfg.family == "audio":
            import repro.models.transformer as T
            import repro.models.layers as L
            frames = fb["frames"]
            enc = frames + T._sinusoid(frames.shape[1], cfg.d_model,
                                       frames.dtype)
            enc, _ = T.stack(params["layers"]["enc"], enc, cfg, RUN32,
                             kind="dense",
                             positions=jnp.arange(frames.shape[1]),
                             causal=False)
            db["enc_out"] = L.rms_norm(enc, params["layers"]["enc_ln"],
                                       cfg.norm_eps)
            db.pop("frames", None)
        lg, caches = m.decode_step(params, db, caches)
        lgs.append(lg[:, 0])
    err = float(jnp.max(jnp.abs(lg_full - jnp.stack(lgs, 1))))
    assert err < 5e-4, f"{arch}: decode mismatch {err}"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_count_analytic_matches_init(arch):
    cfg = ARCHS[arch].reduced()
    m = build_model(cfg, RUN32)
    params = m.init(KEY)
    real = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert analytic_param_count(cfg) == real
    active = analytic_param_count(cfg, active_only=True)
    assert 0 < active <= real
    if cfg.moe is not None:
        assert active < real


def test_full_configs_match_spec():
    """The full configs carry the exact assigned hyperparameters."""
    c = ARCHS["deepseek-v3-671b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab_size) == \
        (61, 7168, 128, 129280)
    assert c.moe.n_experts == 256 and c.moe.top_k == 8
    assert c.mla.kv_lora_rank == 512
    assert (c.moe.scoring, c.moe.n_group, c.moe.topk_group,
            c.moe.norm_topk_prob, c.moe.routed_scaling_factor) == \
        ("sigmoid", 8, 4, True, 2.5)
    assert c.norm_eps == 1e-6
    y = c.rope_scaling
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1, 1, 1)
    c = ARCHS["mistral-large-123b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (88, 12288, 96, 8, 28672, 32768)
    # param count of mistral-large should be ~123B
    n = analytic_param_count(c)
    assert 110e9 < n < 135e9, n
    n = analytic_param_count(ARCHS["deepseek-7b"])
    assert 6e9 < n < 8e9, n
    n = analytic_param_count(ARCHS["deepseek-v3-671b"])
    assert 600e9 < n < 720e9, n
    n_act = analytic_param_count(ARCHS["deepseek-v3-671b"], active_only=True)
    assert 30e9 < n_act < 45e9, n_act


# -- numerical equivalence of optimized paths --------------------------------


def test_blocked_attention_matches_full():
    ks = jax.random.split(KEY, 3)
    B, S, H, K, D = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    want = A.full_attention(q, k, v, causal=True)
    got = A.blocked_attention(q, k, v, causal=True, block_q=16, block_kv=8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    zz = A.blocked_attention(q, k, v, causal=True, block_q=16, block_kv=16,
                             zigzag=True)
    np.testing.assert_allclose(zz, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,chunk", [(48, 16), (40, 12), (16, 16)])
def test_ssd_chunked_matches_recurrent(S, chunk):
    ks = jax.random.split(KEY, 5)
    B, H, P, N = 2, 3, 8, 4
    xs = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    Aa = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, H, N))
    Cm = jax.random.normal(ks[4], (B, S, H, N))
    y1, h1 = ssd_recurrent(xs, dt, Aa, Bm, Cm)
    y2, h2 = ssd_chunked(xs, dt, Aa, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h1, h2, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("S,chunk", [(32, 16), (50, 16), (20, 32)])
def test_wkv_chunked_matches_recurrent(S, chunk):
    ks = jax.random.split(KEY, 5)
    B, H, K = 2, 2, 8
    r, k, v = (jax.random.normal(ks[i], (B, S, H, K)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)))
    u = 0.3 * jax.random.normal(ks[4], (H, K))
    y1, s1 = wkv_recurrent(r, k, v, lw, u)
    y2, s2 = wkv_chunked(r, k, v, lw, u, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-4)


def test_mla_absorbed_decode_matches_expanded():
    cfg = ARCHS["deepseek-v3-671b"].reduced()
    p = A.init_mla(KEY, cfg)
    B, S = 2, 8
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    want = A.mla(p, x, cfg, RUN32, causal=True)
    cache = A.init_mla_cache(cfg, B, S, jnp.float32)
    outs = []
    for t in range(S):
        o, cache = A.mla_decode(p, x[:, t:t + 1], cache, cfg, RUN32)
        outs.append(o[:, 0])
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=1e-4,
                               rtol=1e-3)


def test_int8_kv_cache_decode_accuracy():
    """Quantized-KV decode tracks the f32 forward within 5% relative."""
    cfg = ARCHS["deepseek-7b"].reduced()
    runq = RUN32.with_(kv_cache_dtype="int8")
    mf = build_model(cfg, RUN32)
    mq = build_model(cfg, runq)
    p = mf.init(KEY)
    B, S = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    lg_full, _ = mf.forward(p, {"tokens": toks})
    cq = mq.init_caches(B, S)
    assert jax.tree.leaves(cq["k"])[0].dtype == jnp.int8
    lgs = []
    for t in range(S):
        lg, cq = mq.decode_step(p, {"tokens": toks[:, t:t + 1]}, cq)
        lgs.append(lg[:, 0])
    err = float(jnp.max(jnp.abs(lg_full - jnp.stack(lgs, 1))))
    rel = err / float(jnp.max(jnp.abs(lg_full)))
    assert rel < 0.05, rel


def test_moe_dense_vs_ep_capacity():
    """EP sort/scatter dispatch == dropless dense path when capacity is
    ample (single device shard_map over a trivial mesh)."""
    from repro.launch.mesh import make_mesh
    from repro.models import moe as M
    cfg = ARCHS["olmoe-1b-7b"].reduced()
    params = M.init_moe(KEY, cfg)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    dense_out, aux_d, routed_d = M.moe_dense(params, x, cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    import dataclasses
    cfg_hi = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    ep_out, aux_e, routed_e = M.moe_ep(params, x, cfg_hi, RUN32, mesh)
    np.testing.assert_allclose(ep_out, dense_out, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(aux_d, aux_e, atol=1e-5)
    np.testing.assert_array_equal(routed_e, routed_d)
    assert int(routed_d.sum()) == 2 * 16 * cfg.moe.top_k
