"""DeepSeek-V3's published mechanisms in the program, each against a case
worked by hand or against the published formula: the sigmoid router with
its selection bias and group limit, YaRN's frequencies, cos/sin scale and
softmax scale, and the registered chip share."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import MoEConfig, YarnConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M

# 8 experts in 4 groups of 2; keep 2 groups, then the top 2 experts
ROUTER = MoEConfig(n_experts=8, top_k=2, d_ff_expert=4, scoring="sigmoid",
                   n_group=4, topk_group=2, norm_topk_prob=True,
                   routed_scaling_factor=2.5)
LOGITS = np.array([3.0, 1.0, 2.5, -4.0, 0.8, 0.9, -3.0, -3.0], np.float32)


def route_logits(bias=None):
    # x = e_0 picks row 0 of the router: the logits above
    x = jnp.zeros((1, 8)).at[0, 0].set(1.0)
    w = jnp.zeros((8, 8)).at[0].set(LOGITS)
    gates, idx, _ = M.route(w, x, ROUTER,
                            None if bias is None else jnp.asarray(bias))
    return np.asarray(gates[0]), sorted(np.asarray(idx[0]).tolist())


def sig(z):
    return 1.0 / (1.0 + math.exp(-z))


def test_router_group_limit_excludes_plain_top_k():
    """Scores .953 .731 | .924 .018 | .690 .711 | .047 .047: the group of
    expert 2 sums .942, under groups 0 (1.684) and 2 (1.401), so expert 2,
    second best alone, is not chosen; experts 0 and 1 are."""
    assert sorted(np.argsort(-LOGITS)[:2].tolist()) == [0, 2]
    gates, idx = route_logits()
    assert idx == [0, 1]
    s0, s1 = sig(3.0), sig(1.0)
    np.testing.assert_allclose(sorted(gates, reverse=True),
                               [2.5 * s0 / (s0 + s1), 2.5 * s1 / (s0 + s1)],
                               rtol=1e-6)


def test_router_bias_moves_selection_not_gates():
    """+0.05 on expert 5 lifts it (.761) over expert 1 (.731): it is
    chosen, and its gate is its unbiased score .711, renormalised and
    scaled by 2.5."""
    bias = np.zeros(8, np.float32)
    bias[5] = 0.05
    gates, idx = route_logits(bias)
    assert idx == [0, 5]
    s0, s5 = sig(3.0), sig(0.9)
    np.testing.assert_allclose(sorted(gates, reverse=True),
                               [2.5 * s0 / (s0 + s5), 2.5 * s5 / (s0 + s5)],
                               rtol=1e-6)
    assert abs(sum(gates) - 2.5) < 1e-5


def test_softmax_router_keeps_its_behaviour():
    """olmoe's router: softmax over all experts, top-k renormalised, no
    bias leaf, no groups."""
    cfg = get_arch("olmoe-1b-7b")
    assert (cfg.moe.scoring, cfg.moe.n_group, cfg.moe.routed_scaling_factor,
            cfg.moe.held) == ("softmax", 1, 1.0, 64)
    m = MoEConfig(n_experts=8, top_k=2, d_ff_expert=4)
    x = jnp.zeros((1, 8)).at[0, 0].set(1.0)
    w = jnp.zeros((8, 8)).at[0].set(LOGITS)
    gates, idx, _ = M.route(w, x, m)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    e0, e2 = math.exp(3.0), math.exp(2.5)
    np.testing.assert_allclose(sorted(np.asarray(gates[0]), reverse=True),
                               [e0 / (e0 + e2), e2 / (e0 + e2)], rtol=1e-6)


# -- YaRN ----------------------------------------------------------------------

DSV3_YARN = get_arch("deepseek-v3-671b").rope_scaling


def published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV3YarnRotaryEmbedding (the model's modeling code), in
    float64."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / \
            (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def test_yarn_inv_freq_against_the_published_formula():
    assert L.yarn_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    got = np.asarray(L.rope_inv_freq(64, 10000.0, DSV3_YARN))
    want = published_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # below the range the original frequency, above it divided by 40
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(L.rope_inv_freq(64, 10000.0)),
                               plain, rtol=1e-6)


def test_yarn_cos_sin_scale():
    pos = jnp.arange(5)
    cos, sin = L.rope_freqs(64, 10000.0, pos, DSV3_YARN)
    ang = np.arange(5)[:, None] * published_inv_freq(64, 10000.0, 40.0,
                                                     4096, 32, 1)
    # mscale = mscale_all_dim = 1: the tables are not scaled
    np.testing.assert_allclose(cos, np.cos(ang), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(ang), rtol=1e-5, atol=1e-6)
    y = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                   mscale=1.0, mscale_all_dim=0.0)
    cos2, _ = L.rope_freqs(64, 10000.0, pos, y)
    np.testing.assert_allclose(cos2, np.cos(ang) * (0.1 * math.log(40) + 1),
                               rtol=1e-5, atol=1e-6)


def test_mla_softmax_scale_carries_mscale_squared():
    want = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert A.mla_softmax_scale(get_arch("deepseek-v3-671b")) == \
        pytest.approx(want, rel=1e-12)
    assert (0.1 * math.log(40) + 1) ** 2 == pytest.approx(1.874, abs=1e-3)
    # a config without rope scaling keeps 1/sqrt(q head dim)
    cfg = get_arch("deepseek-v3-671b").reduced()
    import dataclasses
    plain = dataclasses.replace(cfg, rope_scaling=None)
    assert A.mla_softmax_scale(plain) == \
        (cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim) ** -0.5


def test_ep32_share_is_registered():
    full, share = get_arch("deepseek-v3-671b"), get_arch(
        "deepseek-v3-671b-ep32")
    assert share.moe.n_experts == full.moe.n_experts == 256
    assert (share.moe.held, share.moe.first_held) == (8, 0)
    assert full.moe.held == 256
    assert share.vocab_size == 16160 and share.mtp_depth == 0
    for f in ("d_model", "n_heads", "mla", "rope_scaling", "norm_eps",
              "n_layers"):
        assert getattr(share, f) == getattr(full, f)
    assert share.moe.d_ff_expert == full.moe.d_ff_expert


# (form, rows, unreached): the ragged form with no bound or a bound on
# rows, and the dense form, each also with one held expert no pair reaches
HELD_CASES = [
    pytest.param("ragged", None, False, id="None"),
    pytest.param("ragged", 16, False, id="16"),
    pytest.param("ragged", 12, False, id="12"),
    pytest.param("ragged", 5, False, id="5"),
    pytest.param("ragged", None, True, id="None-unreached"),
    pytest.param("dense", None, False, id="dense"),
    pytest.param("dense", None, True, id="dense-unreached"),
]


@pytest.mark.parametrize("form, rows, unreached", HELD_CASES)
def test_held_experts_rows_bound(form, rows, unreached):
    """The held-experts layer over 8 tokens x top-2 routed among 8 experts,
    4 held from expert 2: pairs routed outside the held range, tokens with
    two held pairs, and (`unreached`) held expert 5 with none. With no
    bound, or one the held pairs fit, it equals each held pair's gated
    SwiGLU summed per token; with fewer rows it drops the held pairs past
    them, in the order sorted by expert. The dense form equals the ragged
    one and routes the same pairs."""
    import jax
    rng = np.random.default_rng(3)
    T, k, d, f, E, first = 8, 2, 6, 5, 4, 2
    xt = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    idx = np.stack([rng.choice(8, k, replace=False) for _ in range(T)])
    if unreached:
        # the tokens routed to 5 hold no 7: still k distinct experts
        idx = np.where(idx == 5, 7, idx)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    wg, wu, wd = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in (
        (E, d, f), (E, d, f), (E, f, d)))
    args = (xt, jnp.asarray(idx), gates, wg, wu, wd, first)
    if form == "dense":
        out, routed = M.held_experts_dense(*args)
        ragged, ragged_routed = M.held_experts(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ragged),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(routed),
                                      np.asarray(ragged_routed))
    else:
        out, routed = M.held_experts(*args, rows)
    held = [(t, j) for e in range(E) for t in range(T) for j in range(k)
            if idx[t, j] == first + e]
    kept = held if rows is None else held[:rows]
    want = np.zeros((T, d), np.float32)
    for t, j in kept:
        e = idx[t, j] - first
        h = jax.nn.silu(xt[t] @ wg[e]) * (xt[t] @ wu[e])
        want[t] += float(gates[t, j]) * np.asarray(h @ wd[e])
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(routed), [(idx == first + e).sum() for e in range(E)])
    assert any(np.isin(idx[t], range(first, first + E)).all()
               for t in range(T))
    assert not np.isin(idx, range(first, first + E)).all()
    # 16 rows hold every pair; the 9 held pairs fit 12 rows, not 5
    assert len(held) == (7 if unreached else 9)
    assert (rows == 5) == (len(kept) < len(held))
    assert (np.asarray(routed) == 0).any() == unreached


V5E = M.PEAKS["TPU v5 lite"]


@pytest.mark.parametrize("T, rows, kind, path", [
    (128, None, "TPU v5 lite", "dense"),     # dsv3.decode: 128 tokens
    (240, None, "TPU v5 lite", "dense"),     # the v5e ridge at bf16: 240.5
    (241, None, "TPU v5 lite", "ragged"),
    (4, None, "TPU v5 lite", "ragged"),      # 32 pairs < 256 experts
    (1024, None, "TPU v5 lite", "ragged"),   # over the ridge
    (128, 40, "TPU v5 lite", "ragged"),      # a bound on rows
    (128, None, "cpu", "ragged"),            # a kind PEAKS lacks
])
def test_experts_path(T, rows, kind, path):
    """The held experts run dense only while every held expert expects a
    pair, nothing bounds the rows and the tokens are at most the chip's
    ridge; DeepSeek-V3's top-8 of 256 at bf16."""
    assert M.experts_path(T, 8, 256, rows, M.PEAKS.get(kind), 2) == path


@pytest.fixture
def registry():
    from repro.telemetry import MetricsRegistry, set_default_registry
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    yield reg
    set_default_registry(prev)


def counts(reg):
    c = reg.snapshot()["counters"]
    return (c.get("moe.experts.dense", 0), c.get("moe.experts.ragged", 0))


def test_decode_step_same_on_either_path(monkeypatch, registry):
    """`Model.decode_step` of the reduced EP32 share (4 of 8 experts held,
    top-2) in bfloat16 gives the same logits and routes the same pairs
    with the rule sending the held experts either way; each trace counts
    the path it took."""
    import jax
    from repro.configs.base import RunConfig
    from repro.models.model import Model
    cfg = get_arch("deepseek-v3-671b-ep32").reduced()
    model = Model(cfg, RunConfig(attn_impl="full", remat="nothing"))
    params = model.init(jax.random.PRNGKey(0))
    B = 8
    caches = model.init_caches(B, 16)
    batch = {"tokens": jnp.arange(1, B + 1, dtype=jnp.int32)[:, None] * 7}
    got = {}
    for path in ("ragged", "dense"):
        monkeypatch.setattr(M, "experts_path", lambda *a, p=path: p)
        before = counts(registry)
        logits, _, routed = jax.jit(
            lambda p, b, c: model.decode_step(p, b, c, routed=True))(
                params, batch, caches)
        after = counts(registry)
        taken = [a - b for a, b in zip(after, before)]
        assert taken[("dense", "ragged").index(path)] > 0
        assert taken[("ragged", "dense").index(path)] == 0
        got[path] = (np.asarray(logits, np.float32), np.asarray(routed))
    assert got["dense"][1].sum() > 0
    np.testing.assert_array_equal(got["dense"][1], got["ragged"][1])
    np.testing.assert_allclose(got["dense"][0], got["ragged"][0],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("impl, T, ragged", [
    ("dense", 128, False),      # decode shapes: every held expert reached
    ("dense", 1024, True),      # over the ridge
    ("ep_tp", 128, True),       # the shard bodies bound their rows
    ("ep_a2a", 128, True),
])
def test_ragged_dot_where_the_rule_keeps_it(monkeypatch, registry, impl, T,
                                            ragged):
    """On a v5e (its peaks patched in for the host's device) the one-device
    layer traces no `ragged_dot` at decode shapes, and still does over the
    ridge; the expert-parallel shard bodies always do, and never consult
    the rule."""
    import dataclasses
    import jax
    from repro.configs.base import RunConfig
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(M, "_device_peaks", lambda: V5E)
    cfg = get_arch("deepseek-v3-671b-ep32").reduced()   # top-2 of 8
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           impl=impl))
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((T // 4, 4, cfg.d_model), jnp.bfloat16)
    mesh = None if impl == "dense" else make_mesh((1, 1), ("data", "model"))
    run = RunConfig(attn_impl="full", remat="nothing")
    text = str(jax.make_jaxpr(lambda p, x: M.moe(p, x, cfg, run, mesh))(
        params, x))
    assert ("ragged_dot" in text) == ragged
    dense, rag = counts(registry)
    assert (dense, rag) == ((0, 0) if impl != "dense" else
                            (0, 1) if ragged else (1, 0))
