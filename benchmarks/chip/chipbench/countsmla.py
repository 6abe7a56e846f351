"""Operations and bytes of one decode step of DeepSeek-V3's chip share
(MLA attention, dense MLPs, the router, the shared and the held experts,
the head over the vocabulary slice), counted from the configuration
file's `as_run` by component. The work the step needs, not what the code
does: the absorbed attention core at each slot's filled positions only,
the held experts for the pairs routed to them only. A multiply-add counts
2 FLOPs.

The absorbed form a decode step computes per token and layer: q_nope is
taken into the latent through `wuk`, scores are q_lat . c_kv (kv_lora)
plus q_rope . k_rope (rope) at every filled position, the context is
p . c_kv, and `wuv` takes it out of the latent: every weight of the
layer once, and heads x (2 kv_lora + rope) multiply-adds a position.
"""
from __future__ import annotations

from typing import Dict


def _sizes(c: Dict):
    return (c["d_model"], c["n_heads"], c["mla.q_lora_rank"],
            c["mla.kv_lora_rank"], c["mla.qk_nope_dim"], c["mla.qk_rope_dim"],
            c["mla.v_head_dim"])


def mla_weights(c: Dict) -> int:
    """Matrix parameters of one MLA layer (its norm scales excluded)."""
    d, H, q, r, nope, ro, v = _sizes(c)
    return (d * q + q * H * (nope + ro) + d * r + d * ro +
            r * H * nope + r * H * v + H * v * d)


def mla_norms(c: Dict) -> int:
    return c["mla.q_lora_rank"] + c["mla.kv_lora_rank"]


def latent_row(c: Dict) -> int:
    """Cached values per token and layer: the latent and the rope key."""
    return c["mla.kv_lora_rank"] + c["mla.qk_rope_dim"]


def core_macs(c: Dict) -> int:
    """Multiply-adds of the absorbed core per filled position, token and
    layer."""
    _, H, _, r, _, ro, _ = _sizes(c)
    return H * (2 * r + ro)


def attn_flops(c: Dict, slots: int, positions: int) -> float:
    """The MLA of every layer for `slots` tokens whose filled positions sum
    to `positions`."""
    return 2.0 * c["n_layers"] * (slots * mla_weights(c) +
                                  core_macs(c) * positions)


def attn_bytes(c: Dict, slots: int, positions: int, param_item: int,
               kv_item: int) -> float:
    """Every MLA weight as stored, the latent cache up to the filled
    positions, and the row each slot writes."""
    return c["n_layers"] * ((mla_weights(c) + mla_norms(c)) * param_item +
                            (positions + slots) * latent_row(c) * kv_item)


def expert_weights(c: Dict) -> int:
    """Matrix parameters of one routed expert."""
    return 3 * c["d_model"] * c["moe.d_ff_expert"]


def moe_fixed(c: Dict) -> int:
    """Parameters every token of a MoE layer passes: the router and the
    shared expert."""
    return c["d_model"] * c["moe.n_experts"] + \
        c["moe.n_shared_experts"] * expert_weights(c)


def moe_layers(c: Dict) -> int:
    return c["n_layers"] - c["moe.first_dense_layers"]


def experts_flops(c: Dict, slots: int, held_routes: int) -> float:
    """The router and the shared expert of every MoE layer for `slots`
    tokens, and the held experts for `held_routes` routed pairs."""
    return 2.0 * (moe_layers(c) * slots * moe_fixed(c) +
                  held_routes * expert_weights(c))


def experts_bytes(c: Dict, experts_hit: int, param_item: int) -> float:
    """The router with its bias and the shared expert of every MoE layer,
    and each held expert reached, as stored."""
    return param_item * (moe_layers(c) * (moe_fixed(c) + c["moe.n_experts"])
                         + experts_hit * expert_weights(c))


def dense_mlp_params(c: Dict) -> int:
    return 3 * c["d_model"] * c["moe.d_ff_dense"]


def step_flops(c: Dict, slots: int, positions: int, held_routes: int) -> float:
    """The whole step: the MLA of every layer, the dense MLPs, the expert
    layers and the head over the vocabulary slice (the embedding is a
    lookup)."""
    rest = c["moe.first_dense_layers"] * dense_mlp_params(c) + \
        c["vocab_size"] * c["d_model"]
    return attn_flops(c, slots, positions) + \
        experts_flops(c, slots, held_routes) + 2.0 * slots * rest


def step_bytes(c: Dict, slots: int, positions: int, experts_hit: int,
               param_item: int, kv_item: int) -> float:
    """What the whole step must read and write: the MLA of every layer
    (`attn_bytes`), the expert layers (`experts_bytes`), the dense MLPs,
    the head over the vocabulary slice, the norm scales and the embedding
    rows of the stepped tokens, as stored."""
    d = c["d_model"]
    rest = c["moe.first_dense_layers"] * dense_mlp_params(c) + \
        c["vocab_size"] * d + (2 * c["n_layers"] + 1) * d + slots * d
    return attn_bytes(c, slots, positions, param_item, kv_item) + \
        experts_bytes(c, experts_hit, param_item) + param_item * rest
