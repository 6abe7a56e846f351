"""Mixture-of-Experts: top-k router + two execution paths.

``dense``  — dropless reference: every expert runs over all tokens with a
             gate mask. O(E * T * d * ff) — only for tests / tiny configs.
``ep_tp``  — production path: experts sharded over the 'model' mesh axis
             (expert parallelism folded into tensor parallelism). Activations
             at the MoE input are replicated over 'model' (standard Megatron
             layer boundary), so each model shard *already owns* every token:
             dispatch is a purely local sort/gather into (E_local, C, d)
             capacity buffers, expert FFNs run as batched local matmuls, and
             the combine psum over 'model' replaces the row-parallel
             all-reduce a dense MLP would need anyway — zero extra
             collectives vs dense TP, and zero one-hot-einsum FLOPs (the
             GShard dispatch einsum would cost ~E*C/(k*ff) times the useful
             expert compute: 400x for 256-expert top-8 — see DESIGN.md).

Optionally (RunConfig.fsdp_experts) expert weights are stored sharded over
'data' along the ff dim (ZeRO-3 style) and all-gathered transiently per
layer inside the shard_map body.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig, RunConfig
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, m.n_experts)),
        "w_gate": L.dense_init(ks[1], (m.n_experts, d, m.d_ff_expert)),
        "w_up": L.dense_init(ks[2], (m.n_experts, d, m.d_ff_expert)),
        "w_down": L.dense_init(ks[3], (m.n_experts, m.d_ff_expert, d),
                               in_axis_size=m.d_ff_expert),
    }
    if m.n_shared_experts:
        p["shared"] = L.init_mlp(
            ks[4], d, m.d_ff_expert * m.n_shared_experts, "swiglu")
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(router_w, x, m: MoEConfig):
    """x: (T, d) -> gates (T, k) normalized, idx (T, k), aux load-balance loss.

    Softmax router with top-k renormalization (OLMoE); the DeepSeek-V3
    sigmoid+bias variant differs only in the score nonlinearity — the
    balancing aux term below is the standard switch-style load loss.
    """
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # aux: E * mean(frac_tokens_e * mean_prob_e)
    E = m.n_experts
    onehot = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    frac = jnp.mean(onehot, axis=0)
    mprob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mprob)
    return gates.astype(x.dtype), idx, aux


# ---------------------------------------------------------------------------
# dense (dropless) reference path
# ---------------------------------------------------------------------------


def moe_dense(params, x, cfg: ModelConfig):
    """x: (B,S,d). Every expert processes all tokens; gate-masked combine."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    gates, idx, aux = route(params["router"], xt, m)
    # combine weights (T, E)
    comb = jnp.zeros((B * S, m.n_experts), x.dtype)
    t = jnp.arange(B * S)
    for j in range(m.top_k):
        comb = comb.at[t, idx[:, j]].add(gates[:, j])
    g = jnp.einsum("td,edf->tef", xt, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xt, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", y, comb)
    out = out.reshape(B, S, d)
    if m.n_shared_experts:
        out = out + L.mlp(params["shared"], x, "swiglu")
    return out, aux


# ---------------------------------------------------------------------------
# EP path: local sort/gather dispatch, experts over 'model'
# ---------------------------------------------------------------------------


def _local_expert_ffn(w_gate, w_up, w_down, xb):
    """xb: (E_local, C, d) capacity buffers -> (E_local, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", xb, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xb, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _dispatch_local(xt, idx, gates, e_lo, E_local: int, C: int):
    """Gather tokens assigned to experts [e_lo, e_lo+E_local) into capacity
    buffers. xt: (T, d); idx/gates: (T, k); e_lo may be traced (axis_index).

    Returns xb (E_l, C, d) token buffers, src (E_l, C) source-token index
    (-1 = empty slot), w (E_l, C) gate weights. Sort-based: O(Tk log Tk)
    dispatch with *no* one-hot einsum FLOPs. Scatters use .add so that the
    masked-out entries (which all target slot (0,0) with value 0) can never
    clobber a real token.
    """
    T, k = idx.shape
    flat_e = idx.reshape(-1)                       # (T*k,)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    le = flat_e - e_lo                             # local expert id
    is_local = (le >= 0) & (le < E_local)
    le_key = jnp.where(is_local, le, E_local)      # sentinel sorts last
    order = jnp.argsort(le_key, stable=True)
    le_s = le_key[order]
    t_s = flat_t[order]
    g_s = flat_g[order]
    counts = jnp.bincount(le_key, length=E_local + 1)[:E_local]
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k)
    rank = pos - starts[jnp.clip(le_s, 0, E_local - 1)]
    valid = (le_s < E_local) & (rank < C)
    be = jnp.where(valid, le_s, 0)
    br = jnp.where(valid, rank, 0)
    xb = jnp.zeros((E_local, C, xt.shape[1]), xt.dtype).at[be, br].add(
        jnp.where(valid[:, None], xt[t_s], 0))
    w = jnp.zeros((E_local, C), gates.dtype).at[be, br].add(
        jnp.where(valid, g_s, 0))
    src = (jnp.zeros((E_local, C), jnp.int32).at[be, br].add(
        jnp.where(valid, t_s + 1, 0)) - 1)
    return xb, src, w


def _moe_ep_body(x, router_w, w_gate, w_up, w_down, shared, *,
                 m: MoEConfig, fsdp: bool, axis_names=("data", "model"),
                 mlp_kind: str = "swiglu"):
    """shard_map body. x: (B_l, S, d) local batch shard, replicated over
    'model'. w_*: (E_local, d, ff[/data]) local expert shards."""
    if fsdp:
        w_gate = lax.all_gather(w_gate, "data", axis=2, tiled=True)
        w_up = lax.all_gather(w_up, "data", axis=2, tiled=True)
        w_down = lax.all_gather(w_down, "data", axis=1, tiled=True)
    B_l, S, d = x.shape
    xt = x.reshape(B_l * S, d)
    gates, idx, aux = route(router_w, xt, m)
    E_local = w_gate.shape[0]
    shard = lax.axis_index("model")
    e_lo = shard * E_local
    T = B_l * S
    C = max(1, int(T * m.top_k * m.capacity_factor / m.n_experts))
    xb, src, w = _dispatch_local(xt, idx, gates, e_lo, E_local, C)
    yb = _local_expert_ffn(w_gate.astype(x.dtype), w_up.astype(x.dtype),
                           w_down.astype(x.dtype), xb)
    # combine: scatter-add back to token buffer, weighted
    out = jnp.zeros((T, d), x.dtype)
    flat_src = src.reshape(-1)
    flat_y = (yb * w[..., None].astype(yb.dtype)).reshape(-1, d)
    ok = flat_src >= 0
    out = out.at[jnp.where(ok, flat_src, 0)].add(
        jnp.where(ok[:, None], flat_y, 0))
    out = lax.psum(out, "model")
    aux = lax.pmean(aux, tuple(axis_names))   # replicated scalar
    out = out.reshape(B_l, S, d)
    if shared:
        out = out + L.mlp(shared, x, mlp_kind)
    return out, aux


def moe_ep(params, x, cfg: ModelConfig, run: RunConfig, mesh):
    """Expert-parallel MoE via shard_map on `mesh` (axes pod?/data/model)."""
    m = cfg.moe
    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    xspec = P(batch_axes, None, None)
    ff_spec = "data" if run.fsdp_experts else None
    body = functools.partial(_moe_ep_body, m=m, fsdp=run.fsdp_experts,
                             axis_names=tuple(mesh.axis_names))
    shared = params.get("shared", {})
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, None),
                  P("model", None, ff_spec), P("model", None, ff_spec),
                  P("model", ff_spec, None), P()),
        out_specs=(xspec, P()),
        check_vma=False)
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"], shared)


# ---------------------------------------------------------------------------
# EP over (model x data): DeepSeek-style all-to-all expert parallelism.
# Experts sharded E/(M*D) per device — no ff-dim FSDP, so no per-microbatch
# weight all-gathers (the dominant collective in the fsdp_experts baseline:
# ~1.4 GiB of expert weights re-gathered per layer per microbatch). Tokens
# travel to their expert's data shard via all_to_all over 'data' (wire =
# 2 * T_local * topk * d bytes per layer) and partial outputs combine with
# the same psum('model') the TP MLP needs anyway.
# ---------------------------------------------------------------------------


def _moe_ep_a2a_body(x, router_w, w_gate, w_up, w_down, shared, *,
                     m: MoEConfig, axis_names, data_axis="data",
                     mlp_kind: str = "swiglu"):
    B_l, S, d = x.shape
    xt = x.reshape(B_l * S, d)
    T = B_l * S
    gates, idx, aux = route(router_w, xt, m)
    E_local = w_gate.shape[0]                 # experts on THIS device
    M = lax.axis_size("model")
    D = lax.axis_size(data_axis)
    m_idx = lax.axis_index("model")
    # expert e lives on (m = e // (D*E_local), d = (e // E_local) % D)
    # this m-shard only handles its own experts; others contribute via the
    # final psum over 'model'
    per_m = D * E_local
    e_lo_m = m_idx * per_m
    le = idx - e_lo_m                          # (T, k) local-to-m expert id
    mine = (le >= 0) & (le < per_m)
    owner_d = jnp.where(mine, le // E_local, D)     # D = sentinel
    slot = jnp.where(mine, le % E_local, 0)
    # send capacity per destination data shard: this m-shard only forwards
    # the 1/M fraction of assignments owned by its experts, spread over D
    # destinations
    C_send = max(1, int(T * m.top_k * m.capacity_factor / (D * M)))
    flat_t = jnp.repeat(jnp.arange(T), m.top_k)
    flat_g = gates.reshape(-1)
    flat_dst = owner_d.reshape(-1)
    flat_slot = slot.reshape(-1)
    # rank within destination bucket (sort-based, as in _dispatch_local)
    order = jnp.argsort(jnp.where(flat_dst < D, flat_dst, D), stable=True)
    dst_s = flat_dst[order]
    t_s = flat_t[order]
    g_s = flat_g[order]
    slot_s = flat_slot[order]
    counts = jnp.bincount(jnp.clip(dst_s, 0, D), length=D + 1)[:D]
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(T * m.top_k) - starts[jnp.clip(dst_s, 0, D - 1)]
    valid = (dst_s < D) & (rank < C_send)
    bd = jnp.where(valid, dst_s, 0)
    br = jnp.where(valid, rank, 0)
    send_x = jnp.zeros((D, C_send, d), xt.dtype).at[bd, br].add(
        jnp.where(valid[:, None], xt[t_s], 0))
    meta = jnp.stack([(t_s + 1).astype(jnp.float32),
                      slot_s.astype(jnp.float32)], -1)
    send_meta = jnp.zeros((D, C_send, 2), jnp.float32).at[bd, br].add(
        jnp.where(valid[:, None], meta, 0))
    # exchange: every shard sends bucket j to data-shard j
    recv_x = lax.all_to_all(send_x, data_axis, 0, 0, tiled=False)
    recv_meta = lax.all_to_all(send_meta, data_axis, 0, 0, tiled=False)
    # recv_*: (D, C_send, ...) — tokens from every source shard
    rx = recv_x.reshape(D * C_send, d)
    rsrc = recv_meta[..., 0].reshape(-1).astype(jnp.int32) - 1  # -1 = empty
    rslot = recv_meta[..., 1].reshape(-1).astype(jnp.int32)
    ok = rsrc >= 0
    # gather into per-local-expert capacity buffers (slack is already in
    # C_send via capacity_factor)
    C_loc = max(1, (D * C_send) // max(E_local, 1))
    C_loc = min(C_loc, D * C_send)
    key = jnp.where(ok, rslot, E_local)
    order2 = jnp.argsort(key, stable=True)
    k_s = key[order2]
    counts2 = jnp.bincount(k_s, length=E_local + 1)[:E_local]
    starts2 = jnp.concatenate(
        [jnp.zeros((1,), counts2.dtype), jnp.cumsum(counts2)[:-1]])
    rank2 = jnp.arange(D * C_send) - starts2[jnp.clip(k_s, 0, E_local - 1)]
    valid2 = (k_s < E_local) & (rank2 < C_loc)
    be = jnp.where(valid2, k_s, 0)
    br2 = jnp.where(valid2, rank2, 0)
    xb = jnp.zeros((E_local, C_loc, d), xt.dtype).at[be, br2].add(
        jnp.where(valid2[:, None], rx[order2], 0))
    yb = _local_expert_ffn(w_gate.astype(x.dtype), w_up.astype(x.dtype),
                           w_down.astype(x.dtype), xb)
    # scatter expert outputs back to the recv layout, then reverse a2a
    y_flat = jnp.zeros((D * C_send, d), x.dtype).at[
        jnp.where(valid2, order2, 0)].add(
        jnp.where(valid2[:, None], yb[be, br2], 0))
    y_send = y_flat.reshape(D, C_send, d)
    y_back = lax.all_to_all(y_send, data_axis, 0, 0, tiled=False)
    # combine at source: weight by gate, scatter-add per token
    out = jnp.zeros((T, d), x.dtype)
    yb_flat = y_back.reshape(-1, d)
    out = out.at[jnp.where(valid, t_s, 0)].add(
        jnp.where(valid[:, None],
                  (yb_flat[bd * C_send + br] *
                   jnp.where(valid, g_s, 0)[:, None].astype(x.dtype)), 0))
    out = lax.psum(out, "model")
    aux = lax.pmean(aux, tuple(axis_names))
    out = out.reshape(B_l, S, d)
    if shared:
        out = out + L.mlp(shared, x, mlp_kind)
    return out, aux


def moe_ep_a2a(params, x, cfg: ModelConfig, run: RunConfig, mesh):
    m = cfg.moe
    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    xspec = P(batch_axes, None, None)
    body = functools.partial(_moe_ep_a2a_body, m=m,
                             axis_names=tuple(mesh.axis_names))
    shared = params.get("shared", {})
    espec = P(("model", "data"), None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, None), espec, espec,
                  P(("model", "data"), None, None), P()),
        out_specs=(xspec, P()),
        check_vma=False)
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"], shared)


def moe(params, x, cfg: ModelConfig, run: RunConfig, mesh=None):
    if mesh is not None and "model" in mesh.axis_names:
        if cfg.moe.impl == "ep_a2a":
            return moe_ep_a2a(params, x, cfg, run, mesh)
        if cfg.moe.impl == "ep_tp":
            return moe_ep(params, x, cfg, run, mesh)
    return moe_dense(params, x, cfg)
