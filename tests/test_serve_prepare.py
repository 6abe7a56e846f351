"""The serving copy's selection rule (`repro.serve.prepare`), on a toy
step built to exercise each case: a leaf is cast only when the step
reads it, everywhere, through a convert to the compute dtype."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.serve.prepare import cast_mask, serving_params

BF16 = jnp.bfloat16


@jax.jit
def _project(w, y):
    return y @ w.astype(BF16)


@jax.custom_jvp
def _sin(x):
    return jnp.sin(x)


_sin.defjvp(lambda p, t: (_sin(p[0]), jnp.cos(p[0]) * t[0]))


def toy_step(p, x, ids):
    def body(c, w):
        return c @ w.astype(BF16), None

    # cast inside a scan under checkpoint: selected
    y, _ = jax.checkpoint(lambda x: lax.scan(body, x, p["stack"]))(x)
    # cast inside a jitted call, and inside a custom_jvp's argument: selected
    y = _project(p["jitted"], y) + _sin(p["jvp"].astype(BF16))[0]
    # picked from its stack by index, then cast: selected
    y = y @ lax.dynamic_index_in_dim(p["shared"], ids[0], 0,
                                     False).astype(BF16)
    # cast in one place, read in float32 in another: kept
    y = y @ p["mixed"].astype(BF16) + jnp.sum(p["mixed"]).astype(BF16)
    # cast to a dtype other than the compute dtype: kept
    y = y @ p["half"].astype(jnp.float16).astype(BF16)
    # gathered, then cast: kept
    y = y + jnp.take(p["table"], ids, axis=0).astype(BF16)

    # a scan's carry, though its body reads it only through a convert:
    # kept, for the carry's dtype is the body's output's
    def carried(c, _):
        return (2 * c.astype(BF16)).astype(jnp.float32), None

    keep, _ = lax.scan(carried, p["carry"], None, length=2)
    y = y + keep.astype(BF16)
    # returned as it is: kept; not read at all: kept
    return y, p["out"]


def toy_params():
    ks = jax.random.split(jax.random.PRNGKey(0), 11)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32)
    return {"stack": n(ks[0], 3, 4, 4), "jitted": n(ks[1], 4, 4),
            "jvp": n(ks[2], 4, 4), "shared": n(ks[3], 2, 4, 4),
            "mixed": n(ks[4], 4, 4), "half": n(ks[5], 4, 4),
            "table": n(ks[6], 5, 4), "carry": n(ks[7], 4),
            "out": n(ks[8], 4), "unused": n(ks[9], 4)}


SELECTED = {"stack", "jitted", "jvp", "shared"}


def toy_args():
    return (jnp.ones((2, 4), BF16), jnp.asarray([1, 3], jnp.int32))


def test_selection_rule_on_a_toy_step():
    p = toy_params()
    mask = cast_mask(toy_step, p, *toy_args(), dtype=jnp.dtype(BF16))
    names = sorted(p)                   # a dict flattens in key order
    assert {k for k, m in zip(names, mask) if m} == SELECTED


def test_serving_params_keep_the_step_bitwise():
    p = toy_params()
    args = toy_args()
    served, mask = serving_params(toy_step, p, *args, dtype=BF16)
    for k in p:
        if k in SELECTED:
            assert served[k].dtype == BF16
            np.testing.assert_array_equal(np.asarray(served[k]),
                                          np.asarray(p[k].astype(BF16)))
        else:
            assert served[k] is p[k]
    step = jax.jit(toy_step)
    for a, b in zip(step(p, *args), step(served, *args)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype,selected", [(jnp.float32, set()),
                                            (jnp.float16, {"half"})])
def test_selection_follows_the_compute_dtype(dtype, selected):
    """At float32 no leaf is converted (a cast to the dtype a leaf has is
    no convert), so nothing is cast; at float16 only the leaf read solely
    through a float16 convert is."""
    p = toy_params()
    served, mask = serving_params(toy_step, p, *toy_args(), dtype=dtype)
    assert {k for k, m in zip(sorted(p), mask) if m} == selected
    assert all(served[k] is p[k] for k in p if k not in selected)
