"""Core layer primitives shared by all architectures.

Pure-functional style: ``init_*`` builds a param pytree (nested dicts of
jnp arrays), ``apply``-style functions take (params, x, ...). Weight layout
conventions (chosen for TP sharding; see sharding/rules.py):

  embed:        (vocab, d_model)
  attn q/k/v:   (d_model, n_heads, d_head)      heads -> 'model'
  attn out:     (n_heads, d_head, d_model)      heads -> 'model'
  mlp up/gate:  (d_model, d_ff)                 ff -> 'model'
  mlp down:     (d_ff, d_model)                 ff -> 'model'
  experts:      (E, ...) leading expert dim     E -> 'model'
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis_size: Optional[int] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LM practice)."""
    if in_axis_size is None:
        in_axis_size = shape[0]
    std = 1.0 / math.sqrt(max(in_axis_size, 1))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def zeros_init(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings: full / partial / 2d (GLM) / none
# ---------------------------------------------------------------------------


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor: 0.1 * mscale * ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(beta_fast: float, beta_slow: float, dim: int,
                          theta: float, original_max: int):
    """The rotary pair indices between which YaRN blends extrapolated and
    interpolated frequencies: the dims that turn `beta_fast` and
    `beta_slow` times over `original_max` positions."""
    def dim_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = math.floor(dim_of(beta_fast))
    high = math.ceil(dim_of(beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_inv_freq(dim: int, theta: float, yarn=None):
    """(dim/2,) inverse frequencies; with `yarn` (a YarnConfig), each pair
    blends the original frequency (below the correction range) with it
    divided by `yarn.factor` (above it), linearly across the range."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if yarn is None:
        return inv
    low, high = yarn_correction_range(yarn.beta_fast, yarn.beta_slow, dim,
                                      theta,
                                      yarn.original_max_position_embeddings)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) /
                    (high - low), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def rope_freqs(dim: int, theta: float, positions, yarn=None):
    """(..., dim/2) cos and sin tables for given positions (any int
    array); with `yarn`, YaRN's frequencies and its cos/sin scale,
    mscale / mscale_all_dim."""
    inv = rope_inv_freq(dim, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., dim/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / \
            yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def apply_rope(x, cos, sin):
    """Rotate pairs (x[..., ::2], x[..., 1::2]). x: (..., S, H, D) with
    cos/sin broadcastable (..., S, 1, D/2)."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def rotary(x, positions, kind: str, fraction: float, theta: float,
           yarn=None):
    """Apply RoPE variant to (B, S, H, D) given positions (B, S) or (S,),
    with YaRN's scaling when `yarn` (a YarnConfig) is given.

    kind: "full"    — rotate all dims
          "partial" — rotate leading `fraction` of dims (nemotron)
          "2d"      — GLM-style: rotate first half of dims with position ids,
                      second quarter-pairs kept — implemented as partial(0.5)
                      over interleaved pairs, which matches ChatGLM's applied
                      form for 1-d text positions.
          "none"
    """
    if kind == "none":
        return x
    d = x.shape[-1]
    rot = d if kind == "full" else int(d * fraction)
    rot = max(2, (rot // 2) * 2)
    if positions.ndim == 1:
        positions = positions[None, :]
    cos, sin = rope_freqs(rot, theta, positions, yarn)  # (B, S, rot/2)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    if rot == d:
        return apply_rope(x, cos, sin)
    xr, xp = x[..., :rot], x[..., rot:]
    return jnp.concatenate([apply_rope(xr, cos, sin), xp], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, kind: str):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "gate": dense_init(ks[0], (d_model, d_ff)),
            "up": dense_init(ks[1], (d_model, d_ff)),
            "down": dense_init(ks[2], (d_ff, d_model), in_axis_size=d_ff),
        }
    # relu2 / gelu: two-matrix MLP
    return {
        "up": dense_init(ks[1], (d_model, d_ff)),
        "down": dense_init(ks[2], (d_ff, d_model), in_axis_size=d_ff),
    }


def mlp(params, x, kind: str):
    if kind == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, params["gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, params["up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    elif kind == "relu2":
        u = jnp.einsum("bsd,df->bsf", x, params["up"].astype(x.dtype))
        h = jnp.square(jax.nn.relu(u))
    elif kind == "gelu":
        u = jnp.einsum("bsd,df->bsf", x, params["up"].astype(x.dtype))
        h = jax.nn.gelu(u)
    else:
        raise ValueError(kind)
    return jnp.einsum("bsf,fd->bsd", h, params["down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------


def init_embed(key, vocab: int, d_model: int):
    return dense_init(key, (vocab, d_model), in_axis_size=d_model)


def embed(table, ids, compute_dtype):
    return jnp.take(table, ids, axis=0).astype(compute_dtype)


def logits(table_or_head, x):
    """x: (B, S, D) -> (B, S, V). Head stored (V, D) (embed layout) or (D, V)."""
    w = table_or_head
    if w.shape[0] == x.shape[-1]:
        return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))


def cross_entropy(lg, labels, z_loss: float = 0.0):
    """Token-mean CE with optional z-loss; labels < 0 are masked."""
    lg = lg.astype(jnp.float32)
    m = jnp.max(lg, axis=-1, keepdims=True)
    lse = m.squeeze(-1) + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1))
    tgt = jnp.take_along_axis(
        lg, jnp.maximum(labels, 0)[..., None], axis=-1).squeeze(-1)
    nll = lse - tgt
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
