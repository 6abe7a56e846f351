"""Weights and inputs from `--seed`, made on the device.

Every leaf of a parameter tree is a function of the seed, the leaf's path
and its row index alone: row `i` of leaf `p` is drawn from
`fold_in(fold_in(key, crc32(p)), i)`. So the program's whole tree is made
in one jitted call, and a plain reference can make any rows of any leaf
again (one layer of a stack, the embedding rows of the tokens it needs)
without reading anything the program holds.

Init rule, by the leaf's last path key: norm scales are ones, gates are
zeros, and every other leaf is normal with std 1/sqrt(fan_in). A row's
fan-in is the product of its leading axes when its last axis is the model
width (an output projection), else its first axis; a one-axis row (an
embedding or head row) has fan-in d_model.
"""
from __future__ import annotations

import math
import zlib
from typing import Tuple

import numpy as np

ONES = ("ln1", "ln2", "norm", "enc_ln", "ln_cross")
ZEROS = ("gate",)


def key_data(seed: int) -> np.ndarray:
    """A threefry key's two words from a seed of up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)


def path_name(path) -> str:
    parts = []
    for p in path:
        k = getattr(p, "key", getattr(p, "name", getattr(p, "idx", None)))
        parts.append(str(k))
    return "/".join(parts)


def fan_in(row_shape: Tuple[int, ...], d_model: int) -> int:
    if len(row_shape) <= 1:
        return d_model
    if row_shape[-1] == d_model:
        return int(np.prod(row_shape[:-1]))
    return int(row_shape[0])


def leaf_rows(kd, name: str, shape: Tuple[int, ...], d_model: int,
              rows=None, dtype=None):
    """Rows `rows` (all when None) of leaf `name` of full shape `shape`,
    as float32 unless `dtype` is given. Traceable."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    last = name.rsplit("/", 1)[-1]
    n = shape[0] if shape else 1
    row_shape = tuple(shape[1:]) if shape else ()
    idx = jnp.arange(n) if rows is None else jnp.asarray(rows)
    if last in ONES or last in ZEROS:
        fill = 1.0 if last in ONES else 0.0
        out = jnp.full((idx.shape[0],) + row_shape, fill, dtype)
        return out if shape else out.reshape(())
    key = jax.random.wrap_key_data(jnp.asarray(kd, jnp.uint32))
    lk = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    std = 1.0 / math.sqrt(max(1, fan_in(row_shape, d_model)))

    def one(i):
        return jax.random.normal(jax.random.fold_in(lk, i), row_shape,
                                 jnp.float32)

    return (std * jax.vmap(one)(idx)).astype(dtype)


def make_params(kd, abstract, d_model: int):
    """The whole tree `abstract` (ShapeDtypeStructs, e.g. from
    `jax.eval_shape(model.init, ...)`) in one jitted call, each leaf in its
    own dtype."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def gen(kd):
        leaves = [leaf_rows(kd, path_name(p), tuple(s.shape), d_model,
                            dtype=s.dtype) for p, s in flat]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(gen)(np.asarray(kd))

