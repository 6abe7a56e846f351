"""Attention variants: GQA (full / blocked-flash / decode), DeepSeek MLA
(train + absorbed-latent decode), and cross-attention for VLM/enc-dec.

The "blocked" path is the XLA flash-style implementation (online softmax,
lax.scan over KV blocks) used for long-sequence prefill/train: activation
memory is O(block) instead of O(S^2). The Pallas kernel in
repro/kernels/flash_attention.py implements the same contract for TPU;
runtime selection is RunConfig.attn_impl.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, RunConfig
from repro.models import layers as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA weights
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return {
        "wq": L.dense_init(ks[0], (d, H, Dh)),
        "wk": L.dense_init(ks[1], (d, K, Dh)),
        "wv": L.dense_init(ks[2], (d, K, Dh)),
        "wo": L.dense_init(ks[3], (H, Dh, d), in_axis_size=H * Dh),
    }


# ---------------------------------------------------------------------------
# softmax attention cores
# ---------------------------------------------------------------------------


def _grouped_scores(q, k):
    """q: (B,Sq,K,G,D), k: (B,Sk,K,D) -> (B,K,G,Sq,Sk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k)


def full_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None):
    """Plain softmax attention. q: (B,Sq,H,D); k,v: (B,Sk,K,D).
    q_offset: absolute position of q[0] (for causal masking w/ cache).
    kv_len: number of valid kv positions (decode) — scalar or (B,)."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, D)
    s = _grouped_scores(qg, k) * (1.0 / math.sqrt(D))
    s = s.astype(jnp.float32)
    Sk = k.shape[1]
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        kpos = jnp.arange(Sk)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        if kv_len.ndim == 0:
            mask = jnp.arange(Sk)[None, :] < kv_len
        else:   # per-row lengths (continuous batching)
            mask = jnp.arange(Sk)[None, None, None, None, :] < \
                kv_len[:, None, None, None, None]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(B, Sq, H, D)


def blocked_attention(q, k, v, *, causal: bool, block_q: int, block_kv: int,
                      q_offset: int = 0, zigzag: bool = False):
    """Flash-style attention: online softmax, scanned over KV blocks.

    Memory: O(B*H*block_q*block_kv) for scores instead of O(Sq*Sk).
    With ``causal`` and ``zigzag=False`` all kv blocks are visited for every
    q block (masked) — ~2x causal FLOP waste, removed by the zigzag schedule
    (see §Perf): q block i is fused with q block nq-1-i so every fused pair
    needs the same number of kv blocks.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    G = H // K
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Sk)
    # pad to block multiples
    pq = (-Sq) % block_q
    pk = (-Sk) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq = (Sq + pq) // block_q
    nk = (Sk + pk) // block_kv
    qg = q.reshape(B, nq, block_q, K, G, D)
    scale = 1.0 / math.sqrt(D)

    if causal and zigzag and nq % 2 == 0 and Sq == Sk and q_offset == 0:
        return _zigzag_causal(qg, k, v, B, nq, block_q, nk, block_kv,
                              K, G, D, Sq, Sk, pq, scale, q.dtype)

    kpos = jnp.arange(nk * block_kv)

    def q_block(qi, qb):
        # qb: (B, block_q, K, G, D)
        def body(carry, ki):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, ki * block_kv, block_kv, 1)
            vb = lax.dynamic_slice_in_dim(v, ki * block_kv, block_kv, 1)
            s = _grouped_scores(qb, kb).astype(jnp.float32) * scale
            if causal:
                qpos = q_offset + qi * block_q + jnp.arange(block_q)
                kp = ki * block_kv + jnp.arange(block_kv)
                s = jnp.where(qpos[:, None] >= kp[None, :], s, NEG_INF)
            else:
                # mask kv padding
                kp = ki * block_kv + jnp.arange(block_kv)
                s = jnp.where(kp[None, :] < Sk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            msafe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
            p = jnp.where(s > NEG_INF / 2,
                          jnp.exp(s - msafe[..., None]), 0.0)
            corr = jnp.where(m > NEG_INF / 2, jnp.exp(m - msafe), 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, K, G, block_q, D), jnp.float32)
        (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nk))
        o = acc / jnp.maximum(l[..., None], 1e-30)
        return o.astype(q.dtype)  # (B,K,G,block_q,D)

    outs = lax.map(lambda args: q_block(*args),
                   (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    # outs: (nq, B, K, G, block_q, D) -> (B, Sq, H, D)
    o = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    o = o.reshape(B, nq * block_q, H, D)
    return o[:, :Sq]


def _zigzag_causal(qg, k, v, B, nq, block_q, nk, block_kv, K, G, D,
                   Sq, Sk, pq, scale, dtype):
    """Causal blocked attention with ~half the masked-FLOP waste removed.

    Fold trick: pair q-block p ("lo") with q-block nq-1-p ("hi"). lo needs
    kv blocks [0, p]; hi needs [0, nq-1-p]; combined need = nq+1 blocks —
    *constant across pairs*. Two lanes per scan step t in [0, T),
    T = ceil((nq+1)/2):

      lane A: serves lo with kv block t while t <= p, then serves hi with
              kv blocks from the top: j = nq - t  (t > p)
      lane B: always serves hi with kv block t (bottom-up)

    Lane A's top-down hi blocks are masked out where they would duplicate
    lane B's bottom-up coverage (j <= T-1) or exceed hi's need (j > nq-1-p).
    Total score work = 2 lanes * T * bq * bkv * (nq/2 pairs)
                     ~= Sq*Sk/2 + O(S*block)  vs  Sq*Sk for the plain path.

    Requires block_q == block_kv (caller guarantees by passing equal blocks
    when zigzag is on), Sq == Sk, no q_offset.
    """
    assert block_q == block_kv, "zigzag requires square blocks"
    half = nq // 2
    T = (nq + 1 + 1) // 2  # ceil((nq+1)/2)

    def one_update(carry, qb, qpos, kv_idx, valid):
        """Online-softmax update of (m,l,acc) for rows qb against kv block
        kv_idx; `valid` scalar bool gates the whole block."""
        m, l, acc = carry
        kb = lax.dynamic_slice_in_dim(k, kv_idx * block_kv, block_kv, 1)
        vb = lax.dynamic_slice_in_dim(v, kv_idx * block_kv, block_kv, 1)
        kp = kv_idx * block_kv + jnp.arange(block_kv)
        s = _grouped_scores(qb, kb).astype(jnp.float32) * scale
        mask = (qpos[:, None] >= kp[None, :]) & valid
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        msafe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - msafe[..., None]), 0.0)
        corr = jnp.where(m > NEG_INF / 2, jnp.exp(m - msafe), 0.0)
        l_new = l * corr + jnp.sum(pexp, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", pexp.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new)

    def pair_block(p):
        lo = qg[:, p].reshape(B, block_q, K, G, D)
        hi = qg[:, nq - 1 - p].reshape(B, block_q, K, G, D)
        lo_pos = p * block_q + jnp.arange(block_q)
        hi_pos = (nq - 1 - p) * block_q + jnp.arange(block_q)

        def body(carry, t):
            (cl, ch) = carry
            # lane A: serves lo (kv block t) while t <= p, afterwards serves
            # hi top-down (kv block nq-t). One real update per lane per step.
            a_is_lo = t <= p
            a_idx_hi = jnp.clip(nq - t, 0, nk - 1)
            a_hi_valid = (a_idx_hi > T - 1) & (a_idx_hi <= nq - 1 - p)
            qb = jnp.where(a_is_lo, lo, hi)
            qpos_a = jnp.where(a_is_lo, lo_pos, hi_pos)
            a_idx = jnp.where(a_is_lo, t, a_idx_hi)
            a_valid = a_is_lo | a_hi_valid
            c_in = jax.tree.map(lambda x, y: jnp.where(a_is_lo, x, y), cl, ch)
            c_out = one_update(c_in, qb, qpos_a,
                               jnp.where(a_valid, a_idx, 0), a_valid)
            cl = jax.tree.map(lambda n, o: jnp.where(a_is_lo, n, o), c_out, cl)
            ch = jax.tree.map(lambda n, o: jnp.where(a_is_lo, o, n), c_out, ch)
            # lane B: always serves hi bottom-up (kv block t)
            b_valid = t <= nq - 1 - p
            ch = one_update(ch, hi, hi_pos, jnp.where(b_valid, t, 0), b_valid)
            return (cl, ch), None

        def fresh():
            m0 = jnp.full((B, K, G, block_q), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, K, G, block_q), jnp.float32)
            a0 = jnp.zeros((B, K, G, block_q, D), jnp.float32)
            return (m0, l0, a0)

        (cl, ch), _ = lax.scan(body, (fresh(), fresh()), jnp.arange(T))

        def finish(c):
            m, l, acc = c
            return (acc / jnp.maximum(l[..., None], 1e-30)).astype(dtype)

        return finish(cl), finish(ch)  # each (B,K,G,bq,D)

    lo_outs, hi_outs = lax.map(pair_block, jnp.arange(half))
    # lo_outs[p] is q block p; hi_outs[p] is q block nq-1-p
    full = jnp.concatenate([lo_outs, hi_outs[::-1]], axis=0)  # (nq,B,K,G,bq,D)
    o = jnp.moveaxis(full, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    o = o.reshape(B, nq * block_q, K * G, D)
    return o[:, :Sq]


def decode_attention(q, k_cache, v_cache, kv_len):
    """Single-step decode. q: (B,1,H,D); caches (B,Smax,K,D); kv_len scalar."""
    return full_attention(q, k_cache, v_cache, causal=False, kv_len=kv_len)


# ---------------------------------------------------------------------------
# int8-quantized KV cache (decode capacity optimization, §Perf-extras):
# halves at-rest HBM vs bf16. Symmetric per-(position, head) scales;
# attention runs chunked over the context so only one dequantized block is
# ever materialized (flash-decoding layout compatible).
# ---------------------------------------------------------------------------


def quantize_kv(x):
    """x: (..., D) -> (int8 values, bf16 scales (..., 1))."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127
                 ).astype(jnp.int8)
    return q, s.astype(jnp.bfloat16)


def decode_attention_q8(q, kq, ks, vq, vs, kv_len, block: int = 4096):
    """Decode attention against an int8 cache, dequantizing block-by-block
    with online softmax. q: (B,1,H,D); kq/vq: (B,S,K,D) int8;
    ks/vs: (B,S,K,1) scales; kv_len: (B,) or scalar."""
    B, _, H, D = q.shape
    S = kq.shape[1]
    K = kq.shape[2]
    G = H // K
    block = min(block, S)
    pad = (-S) % block
    if pad:
        zpad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        kq, vq = jnp.pad(kq, zpad4), jnp.pad(vq, zpad4)
        ks, vs = jnp.pad(ks, zpad4), jnp.pad(vs, zpad4)
    nb = (S + pad) // block
    qg = q.reshape(B, K, G, D).astype(jnp.float32)
    scale = 1.0 / math.sqrt(D)
    kv_len = jnp.asarray(kv_len)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len)

    def body(carry, bi):
        m, l, acc = carry
        sl = lambda a: lax.dynamic_slice_in_dim(a, bi * block, block, 1)
        kb = sl(kq).astype(jnp.float32) * sl(ks).astype(jnp.float32)
        vb = sl(vq).astype(jnp.float32) * sl(vs).astype(jnp.float32)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kb) * scale
        pos = bi * block + jnp.arange(block)
        s = jnp.where(pos[None, None, None, :] <
                      kv_len[:, None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        msafe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - msafe[..., None]), 0.0)
        corr = jnp.where(m > NEG_INF / 2, jnp.exp(m - msafe), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgs,bskd->bkgd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((B, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G), jnp.float32)
    a0 = jnp.zeros((B, K, G, D), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------


def _project_qkv(params, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    q = L.rotary(q, positions, cfg.rope_kind, cfg.rope_fraction, cfg.rope_theta,
                 cfg.rope_scaling)
    k = L.rotary(k, positions, cfg.rope_kind, cfg.rope_fraction, cfg.rope_theta,
                 cfg.rope_scaling)
    return q, k, v


def gqa(params, x, cfg: ModelConfig, run: RunConfig, *, positions=None,
        causal: bool = True):
    """Self-attention over a full sequence (train / prefill)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _project_qkv(params, x, cfg, positions)
    if run.attn_impl == "full":
        o = full_attention(q, k, v, causal=causal)
    elif run.attn_impl == "pallas":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=causal,
                                 block_q=run.attn_block_q,
                                 block_kv=run.attn_block_kv)
    else:
        o = blocked_attention(q, k, v, causal=causal,
                              block_q=run.attn_block_q,
                              block_kv=run.attn_block_kv,
                              zigzag=(run.attn_impl == "zigzag"))
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


def gqa_prefill(params, x, cfg: ModelConfig, run: RunConfig, *,
                positions=None, pad_to: int = 0):
    """Like gqa() but also returns the (k, v) cache content, padded to
    `pad_to` positions (the serve-time max length)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _project_qkv(params, x, cfg, positions)
    if run.attn_impl == "full":
        o = full_attention(q, k, v, causal=True)
    else:
        o = blocked_attention(q, k, v, causal=True,
                              block_q=run.attn_block_q,
                              block_kv=run.attn_block_kv,
                              zigzag=(run.attn_impl == "zigzag"))
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    if pad_to > S:
        pad = ((0, 0), (0, pad_to - S), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return out, (k, v)


def mla_prefill(params, x, cfg: ModelConfig, run: RunConfig, *,
                positions=None, pad_to: int = 0):
    """MLA forward that also emits the latent cache (ckv, kr)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    out = mla(params, x, cfg, run, positions=positions, causal=True)
    with jax.named_scope("mla"):
        ckv, kr = _mla_latent(params, x, cfg, positions)
    if pad_to > S:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad_to - S), (0, 0)))
        kr = jnp.pad(kr, ((0, 0), (0, pad_to - S), (0, 0)))
    return out, (ckv, kr)


def gqa_decode(params, x, cache, cfg: ModelConfig, run: RunConfig):
    """One-token decode against a KV cache.

    cache: {"k": (B,Smax,K,D), "v": ..., "pos": (B,) int32} — pos[b] is the
    slot this token writes for row b (per-row: continuous batching);
    kv_len = pos+1. int8 caches carry "k_scale"/"v_scale" (B,Smax,K,1).
    """
    B = x.shape[0]
    pos = cache["pos"]                       # (B,)
    positions = pos[:, None]
    q, k, v = _project_qkv(params, x, cfg, positions)
    rows = jnp.arange(B)
    if "k_scale" in cache:                   # int8 quantized cache
        kq8, ksc = quantize_kv(k[:, 0])
        vq8, vsc = quantize_kv(v[:, 0])
        kq = cache["k"].at[rows, pos].set(kq8)
        vq = cache["v"].at[rows, pos].set(vq8)
        ks = cache["k_scale"].at[rows, pos].set(ksc)
        vs = cache["v_scale"].at[rows, pos].set(vsc)
        o = decode_attention_q8(q, kq, ks, vq, vs, pos + 1)
        out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
        return out, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs,
                     "pos": pos + 1}
    k_cache = cache["k"].at[rows, pos].set(
        k[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[rows, pos].set(
        v[:, 0].astype(cache["v"].dtype))
    o = decode_attention(q, k_cache, v_cache, pos + 1)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, {"k": k_cache, "v": v_cache, "pos": pos + 1}


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                   quant: bool = False):
    K, Dh = cfg.n_kv_heads, cfg.d_head
    if quant:
        return {"k": jnp.zeros((batch, max_len, K, Dh), jnp.int8),
                "v": jnp.zeros((batch, max_len, K, Dh), jnp.int8),
                "k_scale": jnp.zeros((batch, max_len, K, 1), jnp.bfloat16),
                "v_scale": jnp.zeros((batch, max_len, K, 1), jnp.bfloat16),
                "pos": jnp.zeros((batch,), jnp.int32)}
    return {"k": jnp.zeros((batch, max_len, K, Dh), dtype),
            "v": jnp.zeros((batch, max_len, K, Dh), dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# Cross-attention (vision / enc-dec). KV from media embeddings; for decode the
# media KV is static so it is computed once at prefill and carried in cache.
# ---------------------------------------------------------------------------


def init_cross_attn(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return {
        "wq": L.dense_init(ks[0], (d, H, Dh)),
        "wk": L.dense_init(ks[1], (d, K, Dh)),
        "wv": L.dense_init(ks[2], (d, K, Dh)),
        "wo": L.dense_init(ks[3], (H, Dh, d), in_axis_size=H * Dh),
        "gate": jnp.zeros(()),        # llama-vision tanh gate (0-init)
    }


def cross_attn_kv(params, media):
    k = jnp.einsum("bmd,dhk->bmhk", media, params["wk"].astype(media.dtype))
    v = jnp.einsum("bmd,dhk->bmhk", media, params["wv"].astype(media.dtype))
    return k, v


def cross_attn(params, x, kv, run: RunConfig, gated: bool = True):
    k, v = kv
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if x.shape[1] > 4096:
        o = blocked_attention(q, k, v, causal=False,
                              block_q=run.attn_block_q,
                              block_kv=min(run.attn_block_kv, k.shape[1]))
    else:
        o = full_attention(q, k, v, causal=False)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    if gated:
        out = jnp.tanh(params["gate"]).astype(x.dtype) * out
    return out


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 8)
    qk = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wdq": L.dense_init(ks[0], (d, m.q_lora_rank)),
        "q_norm": jnp.ones((m.q_lora_rank,)),
        "wuq": L.dense_init(ks[1], (m.q_lora_rank, H, qk),
                            in_axis_size=m.q_lora_rank),
        "wdkv": L.dense_init(ks[2], (d, m.kv_lora_rank)),
        "kv_norm": jnp.ones((m.kv_lora_rank,)),
        "wuk": L.dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_dim),
                            in_axis_size=m.kv_lora_rank),
        "wuv": L.dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim),
                            in_axis_size=m.kv_lora_rank),
        "wkr": L.dense_init(ks[5], (d, m.qk_rope_dim)),
        "wo": L.dense_init(ks[6], (H, m.v_head_dim, d),
                           in_axis_size=H * m.v_head_dim),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(q head dim), times YaRN's mscale(factor, mscale_all_dim)^2
    where the rope is YaRN-scaled (DeepSeek-V3: 192^-0.5 x 1.874)."""
    m = cfg.mla
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_q(params, x, cfg, positions):
    m = cfg.mla
    cq = jnp.einsum("bsd,dr->bsr", x, params["wdq"].astype(x.dtype))
    cq = L.rms_norm(cq, params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, params["wuq"].astype(x.dtype))
    q_nope = q[..., :m.qk_nope_dim]
    q_rope = L.rotary(q[..., m.qk_nope_dim:], positions, "full", 1.0,
                      cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _mla_latent(params, x, cfg, positions):
    ckv = jnp.einsum("bsd,dr->bsr", x, params["wdkv"].astype(x.dtype))
    ckv = L.rms_norm(ckv, params["kv_norm"], cfg.norm_eps)
    kr = jnp.einsum("bsd,dr->bsr", x, params["wkr"].astype(x.dtype))
    kr = L.rotary(kr[:, :, None, :], positions, "full", 1.0,
                  cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return ckv, kr


def mla(params, x, cfg: ModelConfig, run: RunConfig, *, positions=None,
        causal: bool = True):
    """MLA over a full sequence: expand latents to per-head K/V and run the
    blocked softmax core with the combined (nope|rope) q/k. Traced under
    the scope `mla`."""
    with jax.named_scope("mla"):
        return _mla(params, x, cfg, run, positions, causal)


def _mla(params, x, cfg, run, positions, causal):
    m = cfg.mla
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    ckv, kr = _mla_latent(params, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["wuk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", ckv, params["wuv"].astype(x.dtype))
    H = cfg.n_heads
    # the cores scale by 1/sqrt(q head dim); YaRN's mscale^2 goes on q
    qk = m.qk_nope_dim + m.qk_rope_dim
    q = jnp.concatenate([q_nope, q_rope], axis=-1) * \
        (mla_softmax_scale(cfg) * math.sqrt(qk))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :], (B, S, H, m.qk_rope_dim))],
        axis=-1)
    # pad v to qk dim so the shared core can be reused, then slice
    if run.attn_impl == "full":
        o = full_attention(q, k, v if v.shape[-1] == qk else
                           jnp.pad(v, ((0, 0),) * 3 + ((0, qk - m.v_head_dim),)),
                           causal=causal)
    else:
        vv = v if v.shape[-1] == qk else \
            jnp.pad(v, ((0, 0),) * 3 + ((0, qk - m.v_head_dim),))
        o = blocked_attention(q, k, vv, causal=causal,
                              block_q=run.attn_block_q,
                              block_kv=run.attn_block_kv)
    o = o[..., :m.v_head_dim]
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


def mla_decode(params, x, cache, cfg: ModelConfig, run: RunConfig):
    """Absorbed-latent decode: cache only (c_kv, k_rope) = kv_lora+rope dims
    per token (DeepSeek-V3's memory saving), absorb wuk into q and wuv into
    the output path. pos: (B,) per-row positions. Traced under the scope
    `mla`."""
    with jax.named_scope("mla"):
        return _mla_decode(params, x, cache, cfg)


def _mla_decode(params, x, cache, cfg):
    B = x.shape[0]
    pos = cache["pos"]                       # (B,)
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(params, x, cfg, positions)      # (B,1,H,*)
    ckv_t, kr_t = _mla_latent(params, x, cfg, positions)    # (B,1,r),(B,1,rope)
    rows = jnp.arange(B)
    ckv = cache["ckv"].at[rows, pos].set(
        ckv_t[:, 0].astype(cache["ckv"].dtype))
    kr = cache["kr"].at[rows, pos].set(kr_t[:, 0].astype(cache["kr"].dtype))
    # absorb: q_lat (B,1,H,r) = q_nope @ wuk^T
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["wuk"].astype(x.dtype))
    s = (jnp.einsum("bshr,btr->bhst", q_lat, ckv.astype(x.dtype)) +
         jnp.einsum("bshk,btk->bhst", q_rope, kr.astype(x.dtype)))
    s = s.astype(jnp.float32) * mla_softmax_scale(cfg)
    s = jnp.where(jnp.arange(ckv.shape[1])[None, None, None, :] <=
                  pos[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", p, ckv.astype(x.dtype))   # latent ctx
    o = jnp.einsum("bshr,rhk->bshk", ctx, params["wuv"].astype(x.dtype))
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, {"ckv": ckv, "kr": kr, "pos": pos + 1}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
            "pos": jnp.zeros((batch,), jnp.int32)}
