"""Operations and bytes the cells' steps need, counted from the
configuration files' sizes by component, with no recompute and no
padding. A multiply-add counts 2 FLOPs.

The keys of `c` are the program's field names as a configuration file's
`as_run` gives them (`d_model`, `n_heads`, ...).
"""
from __future__ import annotations

from typing import Dict


def _attn_proj(c: Dict) -> int:
    d, H, K = c["d_model"], c["n_heads"], c["n_kv_heads"]
    Dh = d // H
    return d * H * Dh + 2 * d * K * Dh + H * Dh * d


def _mlp(c: Dict) -> int:
    mats = 3 if c.get("mlp_kind", "swiglu") == "swiglu" else 2
    return mats * c["d_model"] * c["d_ff"]


def dense_layer_params(c: Dict) -> int:
    """Matrix parameters of one decoder block (norm scales excluded)."""
    return _attn_proj(c) + _mlp(c)


def decode_flops_per_token(c: Dict) -> float:
    """2 x the matrix parameters a token passes through: every block and
    the output head (the embedding is a lookup)."""
    return 2.0 * (c["n_layers"] * dense_layer_params(c) +
                  c["vocab_size"] * c["d_model"])


def decode_step_bytes(c: Dict, param_itemsize: int, kv_itemsize: int,
                      kv_positions: int) -> float:
    """Bytes one decode step must read: every stored parameter but the
    embedding (of which it gathers one row per token), and the keys and
    values of `kv_positions` filled positions summed over the batch."""
    d, V = c["d_model"], c["vocab_size"]
    params = c["n_layers"] * (dense_layer_params(c) + 2 * d) + V * d + d
    kv = c["n_layers"] * 2 * c["n_kv_heads"] * (d // c["n_heads"])
    return params * param_itemsize + kv_positions * kv * kv_itemsize

