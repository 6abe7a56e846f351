"""Pallas kernels compiled for a described TPU v5e at the widths of the
configs that use them: what interpret mode cannot check (the (8, 128)
block tiling rule, operations Mosaic cannot lower). No chip is needed: the
TPU compiler compiles for a topology that is described, not attached.

All such compiles stay in this one file, and the topology is described in
a fixture: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops

BF16, F32 = jnp.bfloat16, jnp.float32
DS, WH = get_arch("deepseek-7b"), get_arch("whisper-small")
ZB, RW = get_arch("zamba2-7b"), get_arch("rwkv6-7b")
H_SSD = ZB.d_model * ZB.ssm.expand // ZB.ssm.head_dim
H_WKV, K_WKV = RW.n_heads, RW.d_model // RW.n_heads


def _attn(B, S, H, D):
    return [((B, S, H, D), BF16)] * 3


# name -> (kernel, argument shapes and dtypes); the shapes of chip_smoke.py
CASES = {
    "flash_attention_d128": (
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
        _attn(1, 2048, DS.n_heads, DS.d_head)),
    "flash_attention_d64": (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=False,
                                            interpret=False),
        _attn(2, WH.encdec.enc_len, WH.n_heads, WH.d_head)),
    "rmsnorm": (
        lambda x, s: ops.rmsnorm(x, s, interpret=False),
        [((4, 2048, DS.d_model), BF16), ((DS.d_model,), F32)]),
    "ssd": (
        lambda *a: ops.ssd(*a, chunk=ZB.ssm.chunk, interpret=False)[0],
        [((1, 1024, H_SSD, ZB.ssm.head_dim), BF16),
         ((1, 1024, H_SSD), F32), ((H_SSD,), F32),
         ((1, 1024, H_SSD, ZB.ssm.d_state), BF16),
         ((1, 1024, H_SSD, ZB.ssm.d_state), BF16)]),
    "wkv6": (
        lambda *a: ops.wkv6(*a, interpret=False)[0],
        [((1, 512, H_WKV, K_WKV), BF16)] * 3 +
        [((1, 512, H_WKV, K_WKV), F32), ((H_WKV, K_WKV), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    kernel, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
