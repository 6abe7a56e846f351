"""The serving form of a parameter tree: every leaf the step reads only
through a convert to the compute dtype, converted once.

The model code reaches each matmul through `w.astype(x.dtype)`, so a step
given float32 weights converts the whole stack on every call: XLA lifts
the convert out of the layer scan but not out of the program. Handing
the step `round(w)` instead gives every dot the same operands (a convert
to the dtype an array already has is no convert), without the convert.

Which leaves may be handed over converted is read from the step's own
jaxpr, not from their names: a leaf is cast when it is used, and every
use is a `convert_element_type` to the compute dtype. Uses are followed
into the bodies of `scan` (a stacked leaf's use is its slice's: a
convert commutes with the slice), `jit`, `checkpoint`, `custom_jvp` and
`custom_vjp`, and through the slices and reshapes that only move a
leaf's elements (a shared block picked from its stack by index). A leaf
fed to any other primitive, a scan's carry or a program's output, or
read at another dtype anywhere, keeps its stored form: the embedding
table (gathered), norm scales (multiplied in float32), leaves already
stored at the compute dtype.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# higher-order primitives whose operands are their body's inputs, in order
_CALLS = ("jit", "closed_call", "remat2", "custom_jvp_call", "custom_vjp_call")
# primitives that only move the elements of their first operand
_MOVES = ("dynamic_slice", "slice", "reshape", "squeeze", "transpose")


def _body(eqn, i: int):
    """(body jaxpr, its input) that operand `i` of `eqn` becomes, or None
    where the use cannot be followed."""
    name = eqn.primitive.name
    if name == "scan":
        nc, ncarry = eqn.params["num_consts"], eqn.params["num_carry"]
        if nc <= i < nc + ncarry:
            return None                  # the carry's type is fixed
        body = eqn.params["jaxpr"].jaxpr
    elif name in _CALLS:
        body = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        body = getattr(body, "jaxpr", body)
    else:
        return None
    if body is None or len(body.invars) != len(eqn.invars):
        return None
    return body, body.invars[i]


def _converted_only(jaxpr, var, dtype) -> Optional[bool]:
    """True when `var` is used and every use converts it to `dtype`;
    False when some use does not; None when it is not used."""
    if any(v is var for v in jaxpr.outvars):
        return False
    used = None
    for eqn in jaxpr.eqns:
        for i, v in enumerate(eqn.invars):
            if v is not var:
                continue
            if eqn.primitive.name == "convert_element_type":
                if eqn.params["new_dtype"] != dtype:
                    return False
                used = True
                continue
            if eqn.primitive.name in _MOVES and i == 0:
                got = _converted_only(jaxpr, eqn.outvars[0], dtype)
            else:
                inner = _body(eqn, i)
                if inner is None:
                    return False
                got = _converted_only(*inner, dtype)
            if got is False:
                return False
            used = used or got
    return used


def cast_mask(step, params, *args, dtype) -> List[bool]:
    """Per leaf of `params` (flattened): whether `step(params, *args)`
    reads it only through converts to `dtype`. Traces `step`, runs
    nothing."""
    closed = jax.make_jaxpr(step)(params, *args)
    n = len(jax.tree.leaves(params))
    return [bool(_converted_only(closed.jaxpr, v, dtype))
            for v in closed.jaxpr.invars[:n]]


def serving_params(step, params, *args, dtype) -> Tuple[object, List[bool]]:
    """(`params` with every leaf that `step` reads only at `dtype`
    converted to it on the device, the per-leaf mask). Leaves that keep
    their form are the caller's arrays, not copies."""
    dtype = jnp.dtype(dtype)
    mask = cast_mask(step, params, *args, dtype=dtype)
    leaves, tree = jax.tree.flatten(params)
    cast = iter(_convert([a for a, m in zip(leaves, mask) if m], dtype)
                if any(mask) else ())
    served = [next(cast) if m else a for a, m in zip(leaves, mask)]
    return jax.tree.unflatten(tree, served), mask


@functools.partial(jax.jit, static_argnums=1)
def _convert(leaves, dtype):
    """One program for the whole copy: XLA's convert, round to nearest
    even, as the step's own."""
    return [lax.convert_element_type(a, dtype) for a in leaves]
