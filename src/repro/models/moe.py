"""Mixture-of-Experts: one router and one held-experts layer, run on one
device or inside the expert-parallel shard bodies.

The router (`route`) scores every published expert (`MoEConfig.n_experts`,
its width), whatever the device holds. The expert layer holds
`MoEConfig.held` experts from `first_held` and adds, for each pair
(token, expert) routed to one of them, the gated expert output; pairs
routed elsewhere add nothing here. It has two forms, one algorithm with
one numerics order (each expert's output rounded to the compute dtype,
the gate applied after the down projection, the sum in float32):

``held_experts``        the pairs sorted by held expert and run through
                        `lax.ragged_dot`, so the matmul rows follow the
                        pairs: bound by FLOPs where tokens are many, and
                        it can bound its rows (`rows`) and drop the rest.
                        XLA runs it as a custom call, which copies a
                        weight operand that is a slice (a scan's layer).
``held_experts_dense``  every token through every held expert, by dots
                        batched over the expert on both operands, and the
                        pairs not routed there weighted 0. Its FLOPs grow
                        with tokens x held experts, but while the tokens
                        are few it is bound by the weights' read, which
                        is the least the layer can cost once every held
                        expert has a pair; XLA fuses a scan's slice of
                        the stacked weights into its dots.

`moe_dense` takes the dense form where `experts_path` says it costs the
weights' read alone (decode shapes on a chip of `PEAKS`), and the ragged
form elsewhere; the shard bodies bound their rows and always run
`held_experts`.

``dense``  — one device, no exchange (`moe_dense`): the router, the held
             experts and the shared expert. With every expert held it is
             the whole layer; with a share held it is the part that one
             chip of an expert-parallel deployment computes.
``ep_tp``  — experts sharded over the 'model' mesh axis (expert
             parallelism folded into tensor parallelism). Activations at
             the MoE input are replicated over 'model', so each model shard
             already owns every token: it runs the held-experts layer on
             its own experts over rows for `capacity_factor` times its
             even share of the pairs (held pairs past them are dropped,
             so a shard holds no (T*k, d) buffer), and the combine psum
             over 'model' replaces
             the row-parallel all-reduce a dense MLP would need anyway.
             Optionally (RunConfig.fsdp_experts) expert weights are stored
             sharded over 'data' along the ff dim and all-gathered per
             layer inside the shard body.
``ep_a2a`` — experts over (model x data), DeepSeek-style: tokens travel to
             the data shard that holds their expert by all_to_all over
             'data' (send buffers of `capacity_factor`), run through the
             held-experts layer there, and come back the same way; shards
             over 'model' combine by psum.

Every path returns (out, aux, routed): the layer's output, the
load-balance loss and the pairs routed to each held expert, int32
(held,), summed over the batch. Traced under the scopes `moe.route` (the
router) and `moe.experts` (the held experts and the shared expert).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig, RunConfig
from repro.launch.roofline import PEAKS, ChipPeaks
from repro.models import layers as L
from repro.telemetry import default_registry

BIAS = "e_score_correction_bias"


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, m.n_experts)),
        "w_gate": L.dense_init(ks[1], (m.held, d, m.d_ff_expert)),
        "w_up": L.dense_init(ks[2], (m.held, d, m.d_ff_expert)),
        "w_down": L.dense_init(ks[3], (m.held, m.d_ff_expert, d),
                               in_axis_size=m.d_ff_expert),
    }
    if m.scoring == "sigmoid":
        p[BIAS] = jnp.zeros((m.n_experts,))
    if m.n_shared_experts:
        p["shared"] = L.init_mlp(
            ks[4], d, m.d_ff_expert * m.n_shared_experts, "swiglu")
    return p


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(router_w, x, m: MoEConfig, bias=None):
    """x: (T, d) -> gates (T, k), idx (T, k) into all `m.n_experts`, aux.

    Scores in float32: a softmax over the experts (OLMoE) or each
    expert's sigmoid (DeepSeek-V3). Selection adds `bias` (DeepSeek-V3's
    e_score_correction_bias) to the scores and, with `n_group` > 1, keeps
    only the `topk_group` groups whose two best biased scores sum highest;
    the `top_k` best of the experts left are chosen. A gate is its
    expert's unbiased score; the k gates are renormalised to sum 1 when
    `norm_topk_prob`, then scaled by `routed_scaling_factor`. `aux` is the
    switch-style load loss over the scores normalised per token.
    """
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        if m.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        T, E = scores.shape
        if m.n_group > 1:
            per = E // m.n_group
            grouped = choice.reshape(T, m.n_group, per)
            best = lax.top_k(grouped, min(2, per))[0].sum(-1)
            _, keep = lax.top_k(best, m.topk_group)
            kept = jnp.zeros((T, m.n_group), bool).at[
                jnp.arange(T)[:, None], keep].set(True)
            choice = jnp.where(jnp.repeat(kept, per, axis=1), choice,
                               -jnp.inf)
        _, idx = lax.top_k(choice, m.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        if m.norm_topk_prob:
            gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
        gates = gates * m.routed_scaling_factor
        # aux: E * mean(frac_tokens_e * mean_prob_e)
        probs = scores / jnp.sum(scores, -1, keepdims=True)
        frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), 0)
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    return gates.astype(x.dtype), idx, aux


def routed_to(idx, first, n: int):
    """Pairs of `idx` routed to each of experts [first, first + n): int32
    (n,). `first` may be traced."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < n), local, n)
    return jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)


# ---------------------------------------------------------------------------
# the held-experts layer
# ---------------------------------------------------------------------------


def held_experts(xt, idx, gates, w_gate, w_up, w_down, first, rows=None):
    """The held experts' part of the layer. xt: (T, d); idx, gates: (T, k)
    from `route`; w_*: (E_held, ...) the experts [first, first + E_held),
    `first` possibly traced (a shard's offset).

    For every pair (t, j) with idx[t, j] held: gates[t, j] times the
    expert's SwiGLU of xt[t], summed per token -> (T, d), and the pairs
    routed to each held expert, int32 (E_held,). The pairs are sorted by
    held expert, the others last, and each matmul is one `ragged_dot`
    over the groups of held pairs. `rows` bounds the rows the matmuls
    run over: with None, all T * k and no pair is dropped; with fewer,
    the held pairs past the first `rows` are dropped.

    Its work follows the rows, so it is the form for many tokens, where
    the layer is bound by FLOPs, for few pairs (a held expert no pair
    reaches costs no read), and for a bound on rows. Where the weights
    are a scan's slice of a stacked tree, XLA copies each slice before
    the custom call, and with all T * k rows the dot runs over pairs that
    are not held too: at decode shapes `held_experts_dense` costs less.
    """
    with jax.named_scope("moe.experts"):
        T, k = idx.shape
        E = w_gate.shape[0]
        dt = xt.dtype
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < E)
        order = jnp.argsort(jnp.where(held, local, E), stable=True)
        routed = routed_to(idx, first, E)
        dropless = rows is None or rows >= T * k
        if dropless:
            take, sizes = order, routed
        else:
            take = order[:rows]
            sizes = jnp.diff(jnp.minimum(jnp.cumsum(routed), rows),
                             prepend=0)
        xs = xt[take // k]                      # (rows, d) by held expert
        g = lax.ragged_dot(xs, w_gate.astype(dt), sizes)
        u = lax.ragged_dot(xs, w_up.astype(dt), sizes)
        y = lax.ragged_dot(jax.nn.silu(g) * u, w_down.astype(dt), sizes)
        # rows past the held groups are not the product of any expert
        w = jnp.where(held, gates.reshape(-1), 0)[take]
        y = jnp.where(held[take][:, None], y * w[:, None].astype(dt), 0)
        if dropless:
            # back to (token, pair) order; each token's k terms summed in f32
            y = y[jnp.argsort(order)].reshape(T, k, -1)
            out = jnp.sum(y.astype(jnp.float32), axis=1)
        else:
            out = jnp.zeros((T, y.shape[-1]), jnp.float32).at[
                take // k].add(y.astype(jnp.float32))
        out = out.astype(dt)
    return out, routed


def held_experts_dense(xt, idx, gates, w_gate, w_up, w_down, first):
    """`held_experts` with no bound on rows, by dots batched over the held
    expert: every token through every held expert's SwiGLU, read from the
    weights in their stored (E_held, d, f) / (E_held, f, d) layout, each
    expert's output rounded to the compute dtype and weighted by the gate
    of the pair that routed the token to that expert (0 where none did),
    summed over the experts in float32. Same arguments and results.

    It does n_experts / k times the FLOPs the held pairs need and reads
    every held expert's weights, so it pays where the tokens are too few
    for anything but that read to bound it (`experts_path`).
    """
    with jax.named_scope("moe.experts"):
        T, k = idx.shape
        E = w_gate.shape[0]
        dt = xt.dtype
        routed = routed_to(idx, first, E)
        # (E, T): the gate of the pair that routed t to first + e, else 0
        # (top-k picks k distinct experts, so at most one pair a cell)
        hit = (idx - first)[None] == jnp.arange(E)[:, None, None]
        w = jnp.sum(jnp.where(hit, gates[None], 0), axis=-1)
        xe = jnp.broadcast_to(xt, (E,) + xt.shape)
        g = jnp.einsum("etd,edf->etf", xe, w_gate.astype(dt))
        u = jnp.einsum("etd,edf->etf", xe, w_up.astype(dt))
        y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u,
                       w_down.astype(dt))
        y = y * w[..., None].astype(dt)
        out = jnp.sum(y.astype(jnp.float32), axis=0).astype(dt)
    return out, routed


def experts_path(T: int, top_k: int, n_experts: int, rows: Optional[int],
                 peaks: Optional[ChipPeaks], itemsize: int) -> str:
    """"dense" or "ragged": the form of the held-experts layer for T
    tokens, each routed to `top_k` of `n_experts` experts, whose weights
    are stored at `itemsize` bytes, on a chip of `peaks` (None: a kind
    `PEAKS` lacks).

    Dense only where it costs no more than the weights' read:
    - no bound on rows (`rows` None): the dense form has none;
    - every held expert expects a pair (T * top_k >= n_experts), else it
      reads experts the ragged form skips;
    - T at most the chip's ridge, itemsize * flops / (2 * hbm_bw) tokens
      (about 240 for a v5e at bf16): each weight of `itemsize` bytes
      meets all T tokens, 2 * T FLOPs, so up to the ridge the read
      bounds the product.
    How many experts are held cancels from both conditions.
    """
    if rows is not None or peaks is None or T * top_k < n_experts:
        return "ragged"
    ridge = itemsize * peaks.flops / (2 * peaks.hbm_bw)
    return "dense" if T <= ridge else "ragged"


def _device_peaks() -> Optional[ChipPeaks]:
    """Peaks of the default backend's chip; None for a kind `PEAKS` lacks
    (the host CPU among them)."""
    return PEAKS.get(jax.devices()[0].device_kind)


def _shared(shared, x, mlp_kind: str = "swiglu"):
    if not shared:
        return 0
    with jax.named_scope("moe.experts"):
        return L.mlp(shared, x, mlp_kind)


def _router(params):
    return {k: params[k] for k in ("router", BIAS) if k in params}


# ---------------------------------------------------------------------------
# one device, no exchange
# ---------------------------------------------------------------------------


def moe_dense(params, x, cfg: ModelConfig):
    """x: (B,S,d). The router over every expert, the held experts' part and
    the shared expert; nothing is exchanged.

    The held experts run dense (`held_experts_dense`) where `experts_path`
    finds the layer bound by their weights' read on this chip, ragged
    (`held_experts`) elsewhere. The choice is fixed in the traced program:
    each trace counts one to the process registry's counter
    `moe.experts.dense` or `moe.experts.ragged`, once per compile of the
    layer (a scan's body traces once for all its layers), not per call.
    """
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    gates, idx, aux = route(params["router"], xt, m, params.get(BIAS))
    w = (params["w_gate"], params["w_up"], params["w_down"])
    path = experts_path(B * S, m.top_k, m.n_experts, None, _device_peaks(),
                        w[0].dtype.itemsize)
    default_registry().counter(f"moe.experts.{path}").inc()
    held = held_experts_dense if path == "dense" else held_experts
    out, routed = held(xt, idx, gates, *w, m.first_held)
    out = out.reshape(B, S, d) + _shared(params.get("shared"), x)
    return out, aux, routed


# ---------------------------------------------------------------------------
# EP path: experts over 'model'
# ---------------------------------------------------------------------------


def _batch_sum(v, axis_names):
    batch = tuple(a for a in axis_names if a != "model")
    return lax.psum(v, batch) if batch else v


def _moe_ep_body(x, router, w_gate, w_up, w_down, shared, *,
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
    gates, idx, aux = route(router["router"], xt, m, router.get(BIAS))
    E_local = w_gate.shape[0]
    first = m.first_held + lax.axis_index("model") * E_local
    # rows for capacity_factor times this shard's even share of the pairs
    rows = E_local * max(1, int(B_l * S * m.top_k * m.capacity_factor /
                                m.n_experts))
    out, _ = held_experts(xt, idx, gates, w_gate, w_up, w_down, first, rows)
    out = lax.psum(out, "model")
    aux = lax.pmean(aux, tuple(axis_names))   # replicated scalar
    routed = _batch_sum(routed_to(idx, m.first_held, m.held), axis_names)
    out = out.reshape(B_l, S, d) + _shared(shared, x, mlp_kind)
    return out, aux, routed


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
        in_specs=(xspec, P(),
                  P("model", None, ff_spec), P("model", None, ff_spec),
                  P("model", ff_spec, None), P()),
        out_specs=(xspec, P(), P()),
        check_vma=False)
    return fn(x, _router(params), params["w_gate"], params["w_up"],
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


def _moe_ep_a2a_body(x, router, w_gate, w_up, w_down, shared, *,
                     m: MoEConfig, axis_names, data_axis="data",
                     mlp_kind: str = "swiglu"):
    B_l, S, d = x.shape
    xt = x.reshape(B_l * S, d)
    T = B_l * S
    gates, idx, aux = route(router["router"], xt, m, router.get(BIAS))
    E_local = w_gate.shape[0]                 # experts on THIS device
    M = lax.axis_size("model")
    D = lax.axis_size(data_axis)
    m_idx = lax.axis_index("model")
    # expert first_held + e lives on (m = e // (D*E_local),
    # d = (e // E_local) % D); this m-shard only handles its own experts,
    # others contribute via the final psum over 'model'
    per_m = D * E_local
    e_lo_m = m.first_held + m_idx * per_m
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
    # rank within destination bucket (sort-based)
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
    # recv_*: (D, C_send, ...) — one pair per row from every source shard,
    # for local expert `rslot`; empty rows route to no held expert
    rx = recv_x.reshape(D * C_send, d)
    ok = recv_meta[..., 0].reshape(-1) > 0
    rslot = recv_meta[..., 1].reshape(-1).astype(jnp.int32)
    y_flat, _ = held_experts(
        rx, jnp.where(ok, rslot, E_local)[:, None],
        jnp.ones((D * C_send, 1), x.dtype), w_gate, w_up, w_down, 0)
    y_back = lax.all_to_all(y_flat.reshape(D, C_send, d), data_axis, 0, 0,
                            tiled=False)
    # combine at source: weight by gate, scatter-add per token
    out = jnp.zeros((T, d), x.dtype)
    yb_flat = y_back.reshape(-1, d)
    out = out.at[jnp.where(valid, t_s, 0)].add(
        jnp.where(valid[:, None],
                  (yb_flat[bd * C_send + br] *
                   jnp.where(valid, g_s, 0)[:, None].astype(x.dtype)), 0))
    out = lax.psum(out, "model")
    aux = lax.pmean(aux, tuple(axis_names))
    routed = _batch_sum(routed_to(idx, m.first_held, m.held), axis_names)
    out = out.reshape(B_l, S, d) + _shared(shared, x, mlp_kind)
    return out, aux, routed


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
        in_specs=(xspec, P(), espec, espec,
                  P(("model", "data"), None, None), P()),
        out_specs=(xspec, P(), P()),
        check_vma=False)
    return fn(x, _router(params), params["w_gate"], params["w_up"],
              params["w_down"], shared)


def moe(params, x, cfg: ModelConfig, run: RunConfig, mesh=None):
    """(out, aux, routed) by `cfg.moe.impl` on `mesh`; with no mesh, or
    one without a 'model' axis, the layer runs with no exchange."""
    if mesh is not None and "model" in mesh.axis_names:
        if cfg.moe.impl == "ep_a2a":
            return moe_ep_a2a(params, x, cfg, run, mesh)
        if cfg.moe.impl == "ep_tp":
            return moe_ep(params, x, cfg, run, mesh)
    return moe_dense(params, x, cfg)
