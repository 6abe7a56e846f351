"""Plain reference of one chip's share of DeepSeek-V3 (MLA attention, the
sigmoid group-limited router, held experts plus a shared expert), in
float32 at the highest matmul precision, written from the published
equations (arXiv:2412.19437 and the model's config.json). It imports
nothing of the program: the sizes come from the configuration file's
`as_run`, and every weight is made again from the seed by its leaf name
in the program's parameter layout.

Weights (`leaf`, and `make_params` for the program's tree, by the same
rule): norm scales are ones; every other leaf is drawn row by row as
`weights.leaf_rows` draws it (normal, std 1/sqrt(fan-in)), except that an
expert leaf (layers, experts, ...) is drawn expert by expert, so that
each expert's fan-in is its own input width. `weights.leaf_rows` itself
would zero every matrix named `gate` and draw the MLA's `q_norm` and
`kv_norm`. The reference reads each leaf in the stored dtype and
computes in float32.

The reference computes attention in the published expanded form (per-head
keys and values from the latent), YaRN on interleaved rotary pairs, and
the router from its equations: sigmoid scores, selection on the scores
plus `e_score_correction_bias` within the best `topk_group` of `n_group`
groups (a group scores its two best biased scores), gates the chosen
unbiased scores renormalised to 1 and scaled by `routed_scaling_factor`.
Of the routed experts only the held ones add to the result, as on the
chip; the shared expert adds everywhere. It runs layer by layer.

A mode sets the precision: "f32" rounds nothing; "bf16" rounds both
operands of every matrix product and the residual stream after each
layer's additions to bfloat16, the precision the program stores and
computes in; "fp8" rounds the operands to float8_e4m3fn, one precision
lower: the control. `logits` runs several modes side by side, each on
the same weights, drawn once a layer.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refmodels import FP8_MAX, HIGHEST, NEG, padded_vocab, rms_norm
from chipbench.weights import fan_in, path_name

EXPERTS = ("w_gate", "w_up", "w_down")


# -- weights ----------------------------------------------------------------


def _drawn(kd, name: str, shape: Tuple[int, ...], d: int, rows, dtype):
    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32))
    lk = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    row_shape = tuple(shape[1:])
    std = 1.0 / math.sqrt(max(1, fan_in(row_shape, d)))
    idx = jnp.arange(shape[0]) if rows is None else jnp.asarray(rows)

    def one(i):
        return jax.random.normal(jax.random.fold_in(lk, i), row_shape,
                                 jnp.float32)

    return (std * jax.vmap(one)(idx)).astype(dtype)


def leaf(kd, name: str, shape: Tuple[int, ...], d: int, rows=None,
         dtype=jnp.float32):
    """Rows `rows` (all when None) of leaf `name` of full shape `shape`.
    Traceable."""
    last = name.rsplit("/", 1)[-1]
    n = shape[0] if rows is None else len(rows)
    if last.endswith("norm") or last in ("ln1", "ln2"):
        return jnp.ones((n,) + tuple(shape[1:]), dtype)
    if last in EXPERTS:
        L, E = shape[:2]
        flat = (L * E,) + tuple(shape[2:])
        r = None if rows is None else \
            (jnp.asarray(rows)[:, None] * E + jnp.arange(E)).reshape(-1)
        return _drawn(kd, name, flat, d, r, dtype).reshape(
            (n, E) + tuple(shape[2:]))
    return _drawn(kd, name, tuple(shape), d, rows, dtype)


def make_params(kd, abstract, d_model: int):
    """The program's whole tree `abstract` (ShapeDtypeStructs) in one
    jitted call, each leaf in its own dtype, by `leaf`'s rule."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def gen(kd):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(kd, path_name(p), tuple(s.shape), d_model, dtype=s.dtype)
            for p, s in flat])

    return jax.jit(gen)(np.asarray(kd))


# -- the published equations ------------------------------------------------


def rnd(x, mode: str):
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = jnp.clip(x, -FP8_MAX, FP8_MAX)
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, rnd(a, mode), rnd(b, mode), precision=HIGHEST)


def store(x, mode: str):
    """The residual stream as `mode` keeps it between layers."""
    return rnd(x, mode) if mode == "bf16" else x


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: Dict) -> np.ndarray:
    """DeepseekV3YarnRotaryEmbedding's inverse frequencies, in float64."""
    dim, base = c["mla.qk_rope_dim"], c["rope_theta"]
    factor = c["rope_scaling.factor"]
    orig = c["rope_scaling.original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(corr_dim(c["rope_scaling.beta_fast"])), 0)
    high = min(math.ceil(corr_dim(c["rope_scaling.beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def softmax_scale(c: Dict) -> float:
    scale = (c["mla.qk_nope_dim"] + c["mla.qk_rope_dim"]) ** -0.5
    m = yarn_mscale(c["rope_scaling.factor"], c["rope_scaling.mscale_all_dim"])
    return scale * m * m


def rope(x, positions, c: Dict):
    """YaRN rotation of each interleaved pair (x[2i], x[2i+1]) of
    x (..., S, H, D) by pos * inv_freq[i], cos/sin times mscale /
    mscale_all_dim."""
    inv = jnp.asarray(yarn_inv_freq(c), jnp.float32)
    m = yarn_mscale(c["rope_scaling.factor"], c["rope_scaling.mscale"]) / \
        yarn_mscale(c["rope_scaling.factor"], c["rope_scaling.mscale_all_dim"])
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = (m * jnp.cos(ang))[:, None, :], (m * jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def route(h, router, bias, c: Dict, mode: str):
    """(T, d) -> (idx (T, k) into all experts, gates (T, k), tie (T,)).

    `tie` is how near the choice came to another that changes a held
    expert's part: the least of the margin between the k-th and the
    (k+1)-th biased score of the kept groups, where either expert is held,
    and the margin between the `topk_group`-th and the next group score,
    where either group has a held expert; +inf where neither is so."""
    scores = jax.nn.sigmoid(mm("td,de->te", h, router, mode))
    T, E = scores.shape
    G, keep, k = c["moe.n_group"], c["moe.topk_group"], c["moe.top_k"]
    lo, n_held = c["moe.first_held"], c["moe.n_held"]
    biased = scores + bias
    group = jnp.sort(biased.reshape(T, G, E // G), axis=-1)[..., -2:].sum(-1)
    g_order = jnp.argsort(-group, axis=-1)
    top_groups = g_order[:, :keep]
    in_kept = (jnp.arange(G)[None, None, :] ==
               top_groups[:, :, None]).any(1)                   # (T, G)
    masked = jnp.where(jnp.repeat(in_kept, E // G, axis=1), biased, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1)
    idx = order[:, :k]
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if c["moe.norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)

    def edge(values, ids, n, held):
        """Margin between places n-1 and n of `ids`, where either is held."""
        v = jnp.take_along_axis(values, ids[:, n - 1:n + 1], axis=-1)
        near = held(ids[:, n - 1]) | held(ids[:, n])
        return jnp.where(near, v[:, 0] - v[:, 1], jnp.inf)

    per = E // G
    tie = jnp.minimum(
        edge(masked, order, k, lambda e: (e >= lo) & (e < lo + n_held)),
        edge(group, g_order, keep,
             lambda g: (g >= lo // per) & (g <= (lo + n_held - 1) // per)))
    return idx, gates * c["moe.routed_scaling_factor"], tie


def swiglu(h, gate, up, down, mode):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, gate, mode)) *
              mm("td,df->tf", h, up, mode), down, mode)


def expert_layer(h, w: Dict, c: Dict, mode: str):
    """One MoE layer's output for tokens h (T, d) from its weights `w`
    (router, e_score_correction_bias, w_gate/w_up/w_down of the held
    experts, shared/gate, shared/up, shared/down): the held experts' part
    plus the shared expert, the experts each token chose, and how near the
    choice came to a tie (`route`)."""
    idx, gates, tie = route(h, w["router"], w["e_score_correction_bias"],
                            c, mode)
    y = swiglu(h, w["shared/gate"], w["shared/up"], w["shared/down"], mode)
    first = c["moe.first_held"]
    for e in range(w["w_gate"].shape[0]):
        coef = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        y = y + coef[:, None] * swiglu(h, w["w_gate"][e], w["w_up"][e],
                                       w["w_down"][e], mode)
    return y, idx, tie


# -- the model --------------------------------------------------------------


def shapes(c: Dict) -> Dict[str, Tuple[int, ...]]:
    d, H = c["d_model"], c["n_heads"]
    q, r = c["mla.q_lora_rank"], c["mla.kv_lora_rank"]
    nope, ro, v = c["mla.qk_nope_dim"], c["mla.qk_rope_dim"], \
        c["mla.v_head_dim"]
    Ld = c["moe.first_dense_layers"]
    Lm = c["n_layers"] - Ld
    E, held, F = c["moe.n_experts"], c["moe.n_held"], c["moe.d_ff_expert"]
    Fs = F * c["moe.n_shared_experts"]
    Vp = padded_vocab(c["vocab_size"])
    out = {"embed": (Vp, d), "norm": (d,), "head": (Vp, d)}
    for grp, n in (("dense_layers", Ld), ("layers", Lm)):
        out.update({
            f"{grp}/ln1": (n, d), f"{grp}/ln2": (n, d),
            f"{grp}/attn/wdq": (n, d, q), f"{grp}/attn/q_norm": (n, q),
            f"{grp}/attn/wuq": (n, q, H, nope + ro),
            f"{grp}/attn/wdkv": (n, d, r), f"{grp}/attn/kv_norm": (n, r),
            f"{grp}/attn/wuk": (n, r, H, nope),
            f"{grp}/attn/wuv": (n, r, H, v),
            f"{grp}/attn/wkr": (n, d, ro), f"{grp}/attn/wo": (n, H, v, d)})
    Fd = c["moe.d_ff_dense"]
    out.update({"dense_layers/mlp/gate": (Ld, d, Fd),
                "dense_layers/mlp/up": (Ld, d, Fd),
                "dense_layers/mlp/down": (Ld, Fd, d),
                "layers/moe/router": (Lm, d, E),
                "layers/moe/e_score_correction_bias": (Lm, E),
                "layers/moe/w_gate": (Lm, held, d, F),
                "layers/moe/w_up": (Lm, held, d, F),
                "layers/moe/w_down": (Lm, held, F, d),
                "layers/moe/shared/gate": (Lm, d, Fs),
                "layers/moe/shared/up": (Lm, d, Fs),
                "layers/moe/shared/down": (Lm, Fs, d)})
    return out


def logits(kd, c: Dict, tokens: np.ndarray, pick: np.ndarray,
           modes=("f32",), param_dtype=jnp.bfloat16, q_block: int = 256):
    """For each mode of `modes`, side by side on the same weights: float32
    logits over the vocabulary slice at positions `pick` (n, P) of the
    sequences `tokens` (n, S), causal from position 0, (modes, n, P, V);
    the experts each MoE layer chose there, int (modes, MoE layers, n, P,
    top_k); and how near each choice came to a tie (`route`), (modes, MoE
    layers, n, P)."""
    shp = shapes(c)
    d, eps, H = c["d_model"], c["norm_eps"], c["n_heads"]
    nope, v_dim = c["mla.qk_nope_dim"], c["mla.v_head_dim"]
    scale = softmax_scale(c)
    q_block = min(q_block, tokens.shape[1])
    if tokens.shape[1] % q_block:
        raise ValueError(f"{tokens.shape[1]} positions in blocks of {q_block}")

    def w(kd, name, layer=None):
        rows = None if layer is None else jnp.reshape(layer, (1,))
        out = leaf(kd, name, shp[name], d, rows=rows, dtype=param_dtype)
        out = out.astype(jnp.float32)
        return out if layer is None else out[0]

    def attention(a, x, mode):
        n, S, _ = x.shape
        t = x.reshape(n * S, d)
        cq = rms_norm(mm("td,dr->tr", t, a["wdq"], mode), a["q_norm"], eps)
        q = mm("tr,rhk->thk", cq, a["wuq"], mode).reshape(n, S, H, -1)
        ckv = rms_norm(mm("td,dr->tr", t, a["wdkv"], mode), a["kv_norm"], eps)
        kr = mm("td,dr->tr", t, a["wkr"], mode).reshape(n, S, 1, -1)
        k_nope = mm("tr,rhk->thk", ckv, a["wuk"], mode).reshape(n, S, H, -1)
        val = mm("tr,rhk->thk", ckv, a["wuv"], mode).reshape(n, S, H, -1)
        pos = jnp.arange(S)
        q_nope, q_pe = q[..., :nope], jax.vmap(lambda z: rope(z, pos, c))(
            q[..., nope:])
        k_pe = jax.vmap(lambda z: rope(z, pos, c))(kr)[:, :, 0]

        def one(args):
            qn, qp, kn, kp, vv = args
            def block(b):
                lo = b * q_block
                qnb = jax.lax.dynamic_slice_in_dim(qn, lo, q_block, 0)
                qpb = jax.lax.dynamic_slice_in_dim(qp, lo, q_block, 0)
                s = (mm("qhk,thk->hqt", qnb, kn, mode) +
                     mm("qhk,tk->hqt", qpb, kp, mode)) * scale
                qpos = lo + jnp.arange(q_block)
                s = jnp.where(qpos[:, None] >= pos[None, :], s, NEG)
                return mm("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), vv,
                          mode)
            return jax.lax.map(block, jnp.arange(S // q_block)).reshape(
                S, H, v_dim)

        o = jax.lax.map(one, (q_nope, q_pe, k_nope, k_pe, val))
        return mm("shk,hkd->sd", o.reshape(n * S, H, v_dim), a["wo"],
                  mode).reshape(n, S, d)

    def layer_weights(kd, grp, l, more):
        names = [f"attn/{k}" for k in ("wdq", "q_norm", "wuq", "wdkv",
                                       "kv_norm", "wkr", "wuk", "wuv", "wo")]
        out = {k: w(kd, f"{grp}/{k}", l) for k in ["ln1", "ln2"] + names +
               list(more)}
        out["attn"] = {k.split("/", 1)[1]: out.pop(k) for k in names}
        return out

    def mixer(p, x, mode):
        return store(x + attention(p["attn"], rms_norm(x, p["ln1"], eps),
                                   mode), mode)

    @jax.jit
    def embed(kd, tokens):
        rows = leaf(kd, "embed", shp["embed"], d, rows=tokens.reshape(-1),
                    dtype=param_dtype).astype(jnp.float32)
        x = rows.reshape(tokens.shape + (d,))
        return jnp.stack([x] * len(modes))

    @jax.jit
    def dense_layer(kd, xs, l):
        mlp = [f"mlp/{m}" for m in ("gate", "up", "down")]
        p = layer_weights(kd, "dense_layers", l, mlp)
        out = []
        for x, mode in zip(xs, modes):
            x = mixer(p, x, mode)
            h = rms_norm(x, p["ln2"], eps).reshape(-1, d)
            y = swiglu(h, *(p[m] for m in mlp), mode)
            out.append(store(x + y.reshape(x.shape), mode))
        return jnp.stack(out)

    @jax.jit
    def moe_layer(kd, xs, l):
        names = ("router", "e_score_correction_bias", "shared/gate",
                 "shared/up", "shared/down") + EXPERTS
        p = layer_weights(kd, "layers", l, [f"moe/{k}" for k in names])
        e = {k: p[f"moe/{k}"] for k in names}
        out, idx, tie = [], [], []
        for x, mode in zip(xs, modes):
            x = mixer(p, x, mode)
            h = rms_norm(x, p["ln2"], eps).reshape(-1, d)
            y, i, t = expert_layer(h, e, c, mode)
            out.append(store(x + y.reshape(x.shape), mode))
            idx.append(i.reshape(x.shape[:2] + (-1,)))
            tie.append(t.reshape(x.shape[:2]))
        return jnp.stack(out), jnp.stack(idx), jnp.stack(tie)

    @jax.jit
    def head(kd, xs, pick):
        norm, out = w(kd, "norm"), w(kd, "head")[:c["vocab_size"]]
        return jnp.stack([
            mm("npd,vd->npv", rms_norm(jnp.take_along_axis(
                x, pick[..., None], axis=1), norm, eps), out, mode)
            for x, mode in zip(xs, modes)])

    kd = jnp.asarray(kd)
    pick = jnp.asarray(pick)
    xs = embed(kd, jnp.asarray(tokens))
    for l in range(c["moe.first_dense_layers"]):
        xs = dense_layer(kd, xs, jnp.int32(l))
    chosen, ties = [], []
    for l in range(c["n_layers"] - c["moe.first_dense_layers"]):
        xs, idx, tie = moe_layer(kd, xs, jnp.int32(l))
        chosen.append(jnp.take_along_axis(idx, pick[None, ..., None], axis=2))
        ties.append(jnp.take_along_axis(tie, pick[None], axis=2))
    return head(kd, xs, pick), jnp.stack(chosen, 1), jnp.stack(ties, 1)
