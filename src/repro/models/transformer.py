"""Transformer / hybrid / SSM stacks with scan-over-layers.

Every stack is expressed as `stacked params` (leading n_layers axis on every
leaf, built by vmapping the per-layer init) consumed by lax.scan — HLO size
is O(1) in depth, which keeps 100-layer × 512-device dry-run compiles fast.
Remat policy wraps the scan body (RunConfig.remat).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RunConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# remat
# ---------------------------------------------------------------------------


def remat_wrap(fn, policy: str):
    if policy == "nothing":
        return fn
    if policy == "dots":
        # weight matmuls only: saving *batched* dots would stash the
        # attention score matrices and defeat blocked attention's O(block)
        # memory (measured: +16 GiB/dev on deepseek-7b tp4 — §Perf H3/H5)
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    # "boundaries": save only the scan carry (layer inputs)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


# ---------------------------------------------------------------------------
# standard decoder block (dense MLP or MoE)
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: str = "dense",
               d_ff: Optional[int] = None):
    """kind: dense | moe | cross (cross-attention block for VLM)."""
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.ones((cfg.d_model,)), "ln2": jnp.ones((cfg.d_model,))}
    if kind == "cross":
        p["attn"] = A.init_cross_attn(ks[0], cfg)
    elif cfg.attention_kind == "mla":
        p["attn"] = A.init_mla(ks[0], cfg)
    else:
        p["attn"] = A.init_gqa(ks[0], cfg)
    if kind == "moe":
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, d_ff or cfg.d_ff,
                              cfg.mlp_kind)
    return p


def block(params, x, cfg: ModelConfig, run: RunConfig, *, kind="dense",
          mesh=None, positions=None, causal=True, media_kv=None):
    """One transformer block. Returns (x, aux_loss)."""
    h = L.rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind == "cross":
        h = A.cross_attn(params["attn"], h, media_kv, run)
    elif cfg.attention_kind == "mla":
        h = A.mla(params["attn"], h, cfg, run, positions=positions,
                  causal=causal)
    else:
        h = A.gqa(params["attn"], h, cfg, run, positions=positions,
                  causal=causal)
    x = x + h
    h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if kind == "moe":
        h, aux, _ = M.moe(params["moe"], h, cfg, run, mesh)
    else:
        h = L.mlp(params["mlp"], h, cfg.mlp_kind)
    return x + h, aux


def block_decode(params, x, cache, cfg: ModelConfig, run: RunConfig, *,
                 kind="dense", mesh=None, media_kv=None):
    """One-token decode through a block; returns (x, new_cache, routed):
    the pairs routed to each held expert for a MoE block, else None."""
    h = L.rms_norm(x, params["ln1"], cfg.norm_eps)
    if kind == "cross":
        h = A.cross_attn(params["attn"], h, media_kv, run)
        new_cache = cache
    elif cfg.attention_kind == "mla":
        h, new_cache = A.mla_decode(params["attn"], h, cache, cfg, run)
    else:
        h, new_cache = A.gqa_decode(params["attn"], h, cache, cfg, run)
    x = x + h
    h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
    routed = None
    if kind == "moe":
        h, _, routed = M.moe(params["moe"], h, cfg, run, mesh)
    else:
        h = L.mlp(params["mlp"], h, cfg.mlp_kind)
    return x + h, new_cache, routed


# ---------------------------------------------------------------------------
# stacked (scan) application
# ---------------------------------------------------------------------------


def init_stack(key, cfg: ModelConfig, n: int, kind="dense", d_ff=None):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(k, cfg, kind, d_ff))(keys)


def stack(params, x, cfg, run, *, kind="dense", mesh=None, positions=None,
          causal=True, media_kv=None):
    """Scan x through a stacked block group. Returns (x, summed aux)."""
    def body(carry, layer_params):
        h, aux = block(layer_params, carry, cfg, run, kind=kind, mesh=mesh,
                       positions=positions, causal=causal, media_kv=media_kv)
        return h, aux

    if not run.scan_layers:
        aux_total = jnp.zeros((), jnp.float32)
        n = jax.tree.leaves(params)[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params)
            x, aux = block(lp, x, cfg, run, kind=kind, mesh=mesh,
                           positions=positions, causal=causal,
                           media_kv=media_kv)
            aux_total = aux_total + aux
        return x, aux_total

    body = remat_wrap(body, run.remat)
    x, auxs = lax.scan(body, x, params)
    return x, jnp.sum(auxs)


def stack_decode(params, x, caches, cfg, run, *, kind="dense", mesh=None,
                 media_kv=None):
    """Scan one token through a stacked group, threading per-layer caches.
    caches: pytree stacked on axis 0. Returns (x, new caches, routed): the
    pairs routed to each held expert by layer, (n, held), for MoE blocks,
    else None."""
    def body(carry, inp):
        layer_params, cache = inp
        h, new_cache, routed = block_decode(
            layer_params, carry, cache, cfg, run, kind=kind, mesh=mesh,
            media_kv=media_kv)
        return h, (new_cache, routed)

    x, (new_caches, routed) = lax.scan(body, x, (params, caches))
    return x, new_caches, routed


def block_prefill(params, x, cfg: ModelConfig, run: RunConfig, *,
                  kind="dense", mesh=None, positions=None, pad_to=0):
    """Block forward that also returns KV-cache contents."""
    h = L.rms_norm(x, params["ln1"], cfg.norm_eps)
    if cfg.attention_kind == "mla":
        h, kv = A.mla_prefill(params["attn"], h, cfg, run,
                              positions=positions, pad_to=pad_to)
    else:
        h, kv = A.gqa_prefill(params["attn"], h, cfg, run,
                              positions=positions, pad_to=pad_to)
    x = x + h
    h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
    if kind == "moe":
        h, _, _ = M.moe(params["moe"], h, cfg, run, mesh)
    else:
        h = L.mlp(params["mlp"], h, cfg.mlp_kind)
    return x + h, kv


def stack_prefill(params, x, cfg, run, *, kind="dense", mesh=None,
                  positions=None, pad_to=0):
    """Scan a stacked group, collecting per-layer KV caches as scan ys."""
    def body(carry, layer_params):
        h, kv = block_prefill(layer_params, carry, cfg, run, kind=kind,
                              mesh=mesh, positions=positions, pad_to=pad_to)
        return h, kv

    body = remat_wrap(body, run.remat)
    x, kvs = lax.scan(body, x, params)
    return x, kvs


# ---------------------------------------------------------------------------
# RWKV stack
# ---------------------------------------------------------------------------


def init_rwkv_stack(key, cfg: ModelConfig):
    keys = jax.random.split(key, cfg.n_layers)

    def one(k):
        p = R.init_rwkv6(k, cfg)
        p["ln1"] = jnp.ones((cfg.d_model,))
        p["ln2"] = jnp.ones((cfg.d_model,))
        return p

    return jax.vmap(one)(keys)


def rwkv_stack(params, x, cfg, run):
    def body(carry, lp):
        norms = {"ln1": lp["ln1"], "ln2": lp["ln2"]}
        return R.rwkv_block(lp, carry, cfg, run, norms), None

    body = remat_wrap(body, run.remat)
    x, _ = lax.scan(body, x, params)
    return x


def rwkv_stack_decode(params, x, caches, cfg, run):
    def body(carry, inp):
        lp, cache = inp
        norms = {"ln1": lp["ln1"], "ln2": lp["ln2"]}
        h, nc = R.rwkv_block_decode(lp, carry, cache, cfg, run, norms)
        return h, nc

    x, new_caches = lax.scan(body, x, (params, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Zamba2 hybrid stack: groups of `period` Mamba2 blocks + a shared attention
# block (n_shared_sets alternating weight sets, NOT scanned — true weight
# sharing across depth, the Zamba2 trick).
# ---------------------------------------------------------------------------


def init_hybrid(key, cfg: ModelConfig):
    hy = cfg.hybrid
    n_groups = max(1, cfg.n_layers // hy.period)
    ks = jax.random.split(key, 4)
    mamba_keys = jax.random.split(ks[0], n_groups * hy.period)

    def one_m(k):
        p = SSM.init_mamba2(k, cfg)
        p["ln"] = jnp.ones((cfg.d_model,))
        return p

    mamba = jax.vmap(one_m)(mamba_keys)
    mamba = jax.tree.map(
        lambda a: a.reshape(n_groups, hy.period, *a.shape[1:]), mamba)
    shared_keys = jax.random.split(ks[1], hy.n_shared_sets)
    d_ff = hy.shared_d_ff or cfg.d_ff
    shared = jax.vmap(
        lambda k: init_block(k, cfg, "dense", d_ff))(shared_keys)
    return {"mamba": mamba, "shared": shared}


def hybrid_stack(params, x, cfg, run, *, positions=None):
    hy = cfg.hybrid
    n_groups = jax.tree.leaves(params["mamba"])[0].shape[0]
    n_sets = jax.tree.leaves(params["shared"])[0].shape[0]

    def group_body(carry, inp):
        g, mamba_g = inp
        h = carry

        def m_body(c, lp):
            y = SSM.mamba2(lp, L.rms_norm(c, lp["ln"], cfg.norm_eps), cfg, run)
            return c + y, None

        m_body = remat_wrap(m_body, run.remat)
        h, _ = lax.scan(m_body, h, mamba_g)
        sel = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, g % n_sets, 0, False),
            params["shared"])
        h, _ = block(sel, h, cfg, run, kind="dense", positions=positions)
        return h, None

    x, _ = lax.scan(group_body, x, (jnp.arange(n_groups), params["mamba"]))
    return x


def hybrid_stack_decode(params, x, caches, cfg, run):
    """caches: {"mamba": stacked (G,period,...) mamba caches,
    "attn": stacked (G, ...) kv caches}."""
    hy = cfg.hybrid
    n_sets = jax.tree.leaves(params["shared"])[0].shape[0]
    n_groups = jax.tree.leaves(params["mamba"])[0].shape[0]

    def group_body(carry, inp):
        g, mamba_g, mcache_g, acache = inp
        h = carry

        def m_body(c, inp2):
            lp, mc = inp2
            y, nmc = SSM.mamba2_decode(
                lp, L.rms_norm(c, lp["ln"], cfg.norm_eps), mc, cfg, run)
            return c + y, nmc

        h, new_mc = lax.scan(m_body, h, (mamba_g, mcache_g))
        sel = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, g % n_sets, 0, False),
            params["shared"])
        h, new_ac, _ = block_decode(sel, h, acache, cfg, run, kind="dense")
        return h, (new_mc, new_ac)

    x, (new_m, new_a) = lax.scan(
        group_body, x,
        (jnp.arange(n_groups), params["mamba"], caches["mamba"],
         caches["attn"]))
    return x, {"mamba": new_m, "attn": new_a}


# ---------------------------------------------------------------------------
# VLM stack (llama-3.2-vision): groups of (period-1) self-attn blocks + 1
# gated cross-attn block. Media KV computed per cross layer from stub patch
# embeddings.
# ---------------------------------------------------------------------------


def init_vlm(key, cfg: ModelConfig):
    ca = cfg.cross_attn
    n_groups = cfg.n_layers // ca.period
    n_self = ca.period - 1
    ks = jax.random.split(key, 2)
    self_keys = jax.random.split(ks[0], n_groups * n_self)
    selfp = jax.vmap(lambda k: init_block(k, cfg, "dense"))(self_keys)
    selfp = jax.tree.map(
        lambda a: a.reshape(n_groups, n_self, *a.shape[1:]), selfp)
    cross_keys = jax.random.split(ks[1], n_groups)
    crossp = jax.vmap(lambda k: init_block(k, cfg, "cross"))(cross_keys)
    return {"self": selfp, "cross": crossp}


def vlm_stack(params, x, media, cfg, run, *, positions=None, decode_caches=None):
    n_groups = jax.tree.leaves(params["cross"])[0].shape[0]

    def group_body(carry, inp):
        selfp_g, crossp = inp
        h = carry

        def s_body(c, lp):
            y, _ = block(lp, c, cfg, run, kind="dense", positions=positions)
            return y, None

        s_body = remat_wrap(s_body, run.remat)
        h, _ = lax.scan(s_body, h, selfp_g)
        kv = A.cross_attn_kv(crossp["attn"], media)
        h, _ = block(crossp, h, cfg, run, kind="cross", media_kv=kv,
                     positions=positions)
        return h, None

    x, _ = lax.scan(group_body, x, (params["self"], params["cross"]))
    return x


def vlm_stack_decode(params, x, media, caches, cfg, run):
    def group_body(carry, inp):
        selfp_g, crossp, scache_g = inp
        h = carry

        def s_body(c, inp2):
            lp, sc = inp2
            y, nsc, _ = block_decode(lp, c, sc, cfg, run, kind="dense")
            return y, nsc

        h, new_sc = lax.scan(s_body, h, (selfp_g, scache_g))
        kv = A.cross_attn_kv(crossp["attn"], media)
        h, _, _ = block_decode(crossp, h, None, cfg, run, kind="cross",
                               media_kv=kv)
        return h, new_sc

    x, new_caches = lax.scan(
        group_body, x, (params["self"], params["cross"], caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Whisper enc-dec
# ---------------------------------------------------------------------------


def init_encdec(key, cfg: ModelConfig):
    ed = cfg.encdec
    ks = jax.random.split(key, 3)
    enc = init_stack(ks[0], cfg, ed.n_encoder_layers, "dense")

    def one_dec(k):
        kk = jax.random.split(k, 2)
        p = init_block(kk[0], cfg, "dense")
        p["cross"] = A.init_cross_attn(kk[1], cfg)
        p["ln_cross"] = jnp.ones((cfg.d_model,))
        return p

    dec = jax.vmap(one_dec)(jax.random.split(ks[1], cfg.n_layers))
    return {"enc": enc, "dec": dec, "enc_ln": jnp.ones((cfg.d_model,))}


def _dec_block(lp, x, enc_out, cfg, run, positions):
    h, _ = block({k: lp[k] for k in ("ln1", "ln2", "attn",
                                     "mlp" if "mlp" in lp else "moe")},
                 x, cfg, run, kind="dense", positions=positions)
    kv = A.cross_attn_kv(lp["cross"], enc_out)
    c = L.rms_norm(h, lp["ln_cross"], cfg.norm_eps)
    return h + A.cross_attn(lp["cross"], c, kv, run, gated=False)


def encdec_apply(params, frames, tokens_x, cfg, run, *, positions=None):
    """frames: (B, enc_len, d) stub embeddings; tokens_x: (B,S,d) embedded."""
    pos_e = jnp.arange(frames.shape[1])
    enc = frames + _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)
    enc, _ = stack(params["enc"], enc, cfg, run, kind="dense",
                   positions=pos_e, causal=False)
    enc = L.rms_norm(enc, params["enc_ln"], cfg.norm_eps)

    def body(carry, lp):
        return _dec_block(lp, carry, enc, cfg, run, positions), None

    body = remat_wrap(body, run.remat)
    x, _ = lax.scan(body, tokens_x, params["dec"])
    return x


def encdec_decode(params, x, enc_out, caches, cfg, run):
    def body(carry, inp):
        lp, cache = inp
        base = {k: lp[k] for k in ("ln1", "ln2", "attn", "mlp")}
        h, nc, _ = block_decode(base, carry, cache, cfg, run, kind="dense")
        kv = A.cross_attn_kv(lp["cross"], enc_out)
        c = L.rms_norm(h, lp["ln_cross"], cfg.norm_eps)
        h = h + A.cross_attn(lp["cross"], c, kv, run, gated=False)
        return h, nc

    x, new_caches = lax.scan(body, x, (params["dec"], caches))
    return x, new_caches


def _sinusoid(S, d, dtype):
    pos = jnp.arange(S)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)[None]
