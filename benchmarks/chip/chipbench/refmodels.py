"""Plain references of the models the cells run, in float32 at the highest
matmul precision, written from the architectures' equations. They import
nothing of the program: the sizes come from the configuration files, and
every weight is made again from the seed by `weights.leaf_rows`, by the
leaf's name in the program's parameter layout.

`mode="fp8"` is the control: the same computation with both operands of
every matrix product rounded to float8_e4m3fn, the precision below the
bfloat16 the configurations compute in.

Departures of the model as the program builds it from the published one,
which the reference follows (each is noted in PERF.md): rotary pairs
interleaved (x[2i], x[2i+1]) and the RMSNorm epsilon of the configuration
file's `as_run`.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import leaf_rows

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
NEG = -1e30


def padded_vocab(v: int) -> int:
    return v if v % 128 == 0 else (v // 128 + 1) * 128


def rnd(x, mode: str):
    if mode == "fp8":
        x = jnp.clip(x.astype(jnp.float32), -FP8_MAX, FP8_MAX)
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x.astype(jnp.float32)


def mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, rnd(a, mode), rnd(b, mode), precision=HIGHEST)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """Rotate each pair (x[2i], x[2i+1]) of x (..., S, H, D) by angle
    pos / theta^(2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv       # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, causal: bool, mode: str):
    """q (S, H, D), k/v (T, H, D) of one sequence."""
    s = mm("qhd,thd->hqt", q, k, mode) / math.sqrt(q.shape[-1])
    if causal:
        S, T = q.shape[0], k.shape[0]
        s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(T)[None, :], s,
                      NEG)
    p = jax.nn.softmax(s, axis=-1)
    return mm("hqt,thd->qhd", p, v, mode)


# ---------------------------------------------------------------------------
# dense decoder-only LM (deepseek-7b: llama architecture)
# ---------------------------------------------------------------------------


def dense_shapes(c: Dict) -> Dict[str, Tuple[int, ...]]:
    L, d, H, K = c["n_layers"], c["d_model"], c["n_heads"], c["n_kv_heads"]
    Dh, F, Vp = d // H, c["d_ff"], padded_vocab(c["vocab_size"])
    return {"embed": (Vp, d), "norm": (d,), "head": (Vp, d),
            "layers/ln1": (L, d), "layers/ln2": (L, d),
            "layers/attn/wq": (L, d, H, Dh), "layers/attn/wk": (L, d, K, Dh),
            "layers/attn/wv": (L, d, K, Dh), "layers/attn/wo": (L, H, Dh, d),
            "layers/mlp/gate": (L, d, F), "layers/mlp/up": (L, d, F),
            "layers/mlp/down": (L, F, d)}


def dense_logits(kd, c: Dict, tokens: np.ndarray, pick: np.ndarray,
                 mode: str = "f32") -> jnp.ndarray:
    """Float32 logits over the real vocabulary at positions `pick` (n, P)
    of the sequences `tokens` (n, S), causal from position 0. Layer by
    layer, each layer's weights made again from the seed."""
    shapes = dense_shapes(c)
    d, eps = c["d_model"], c["norm_eps"]
    K = c["n_kv_heads"]
    G = c["n_heads"] // K

    def w(kd, name, layer=None):
        rows = None if layer is None else jnp.reshape(layer, (1,))
        out = leaf_rows(kd, name, shapes[name], d, rows=rows)
        return out if layer is None else out[0]

    # the key is an argument, not a constant, so that every seed runs the
    # same compiled programs
    @jax.jit
    def embed(kd, tokens):
        flat = leaf_rows(kd, "embed", shapes["embed"], d,
                         rows=tokens.reshape(-1))
        return flat.reshape(tokens.shape + (d,))

    @jax.jit
    def layer(kd, x, l):
        S = x.shape[1]
        pos = jnp.arange(S)

        def one(xs):
            h = rms_norm(xs, w(kd, "layers/ln1", l), eps)
            q = rope(mm("sd,dhk->shk", h, w(kd, "layers/attn/wq", l), mode),
                     pos, c["rope_theta"])
            k = rope(mm("sd,dhk->shk", h, w(kd, "layers/attn/wk", l), mode),
                     pos, c["rope_theta"])
            v = mm("sd,dhk->shk", h, w(kd, "layers/attn/wv", l), mode)
            k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
            o = attention(q, k, v, True, mode)
            xs = xs + mm("shk,hkd->sd", o, w(kd, "layers/attn/wo", l), mode)
            h = rms_norm(xs, w(kd, "layers/ln2", l), eps)
            g = mm("sd,df->sf", h, w(kd, "layers/mlp/gate", l), mode)
            u = mm("sd,df->sf", h, w(kd, "layers/mlp/up", l), mode)
            return xs + mm("sf,fd->sd", jax.nn.silu(g) * u,
                           w(kd, "layers/mlp/down", l), mode)

        return jax.lax.map(one, x)

    @jax.jit
    def head(kd, x, pick):
        xs = jnp.take_along_axis(x, pick[..., None], axis=1)
        xs = rms_norm(xs, w(kd, "norm"), eps)
        hw = w(kd, "head")[:c["vocab_size"]]
        return mm("npd,vd->npv", xs, hw, mode)

    kd = jnp.asarray(kd)
    x = embed(kd, jnp.asarray(tokens))
    for l in range(c["n_layers"]):
        x = layer(kd, x, jnp.int32(l))
    return head(kd, x, jnp.asarray(pick))
