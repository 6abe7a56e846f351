"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For the 256-chip assigned meshes TP×DP saturates every arch without
pipeline bubbles (DESIGN.md §5), so PP is not in the dry-run presets; this
module provides the mechanism the >4k-chip deployment note refers to, with
correctness tests on a real multi-device mesh (tests/test_distribution.py).

Layout: mesh axis 'pipe' with P stages; the layer stack (L, ...) is split
into P contiguous blocks of L/P layers, stage s holding block s (leading
stacked axis sharded over 'pipe'). Microbatches stream through the classic
GPipe schedule: T = n_micro + P - 1 ticks, stage s working on microbatch
t - s at tick t; activations hop stages with collective_permute. The whole
schedule lives inside one lax.scan, so it jits, differentiates (jax AD
transposes collective_permute to the reverse permutation — backward flows
automatically) and composes with the data/model axes of the same mesh.

Bubble fraction = (P-1)/(T) as usual; choose n_micro >> P.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _pipeline_body(stage_params, x_micro, *, fn: Callable, n_micro: int,
                   axis: str):
    """shard_map body. stage_params: this stage's (L/P, ...) layer slice;
    x_micro: (n_micro, B, S, d) — full input stream, replicated over
    'pipe' (stage 0 reads it; others ignore). Returns (n_micro, B, S, d)
    outputs (valid on every stage after the final broadcast)."""
    n_stages = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    ticks = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        incoming, outputs = carry
        # stage 0 ingests microbatch t (clamped; bubble ticks are masked)
        mb_in = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        x_in = jnp.where(stage == 0, mb_in, incoming)
        y = fn(stage_params, x_in)
        # the last stage emits microbatch t - (P-1)
        out_idx = t - (n_stages - 1)
        emit = (stage == n_stages - 1) & (out_idx >= 0)
        idx = jnp.clip(out_idx, 0, n_micro - 1)
        current = lax.dynamic_index_in_dim(outputs, idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, y, current), idx, 0)
        # hop activations forward
        nxt = lax.ppermute(y, axis, fwd_perm)
        return (nxt, outputs), None

    init = (jnp.zeros_like(x_micro[0]),
            jnp.zeros_like(x_micro))
    (_, outputs), _ = lax.scan(tick, init, jnp.arange(ticks))
    # only the last stage holds real outputs; broadcast via masked psum
    # (ppermute can't fan out one source to all destinations)
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, 0), axis)
    return outputs


def pipeline_apply(fn: Callable, stacked_params, x, mesh, *,
                   n_micro: int, axis: str = "pipe"):
    """Run `x` through the full stacked layer group with the stack split
    over the mesh's `axis` dimension.

    fn(stage_params, x) must apply a (L/P, ...) stacked slice (e.g. a
    lax.scan over its layers). x: (B, S, d); B must divide into n_micro.
    """
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    x_micro = x.reshape(n_micro, B // n_micro, *x.shape[1:])
    body = functools.partial(_pipeline_body, fn=fn, n_micro=n_micro,
                             axis=axis)
    # stacked params: leading layer axis sharded over the pipe axis
    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False)(stacked_params, x_micro)
    return out.reshape(B, *x.shape[1:])
