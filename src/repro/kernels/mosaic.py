"""In-kernel helpers for the two kernels with a decay recurrence (ssd,
wkv6) on the Pallas TPU (Mosaic) lowering: a full-f32 matmul, and
stand-ins for what Mosaic lacks, `cumsum` and reshaping a narrow (1, n)
row into an (n, 1) column."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _iota2(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def dot(a, b, contract=((1,), (0,))):
    """Matmul contracting the `contract` dims of a and b, in full f32. At
    its default precision Mosaic rounds f32 operands to bf16, an error the
    recurrences carry from chunk to chunk: on a v5e, ssd at zamba2-7b's
    widths was off by 0.6 on outputs up to 194."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def cumsum_rows(x):
    """Inclusive prefix sum over axis 0 of an (n, k) f32 tile: the
    lower-triangular ones matrix times x."""
    i, j = _iota2(x.shape[0])
    return dot((i >= j).astype(jnp.float32), x)


def cumsum_row(x):
    """Inclusive prefix sum along a (1, n) f32 row."""
    i, j = _iota2(x.shape[1])
    return dot(x, (i >= j).astype(jnp.float32), ((1,), (1,)))


def row_to_col(row):
    """(1, n) -> (n, 1) by a masked lane reduction."""
    n = row.shape[1]
    i, j = _iota2(n)
    return jnp.sum(jnp.where(i == j, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)
