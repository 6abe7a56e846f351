"""HLO cost extraction: trip-count-aware FLOPs/collectives, roofline math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.launch.hlo_costs import analyze, parse_computations
from repro.launch.roofline import (PEAKS, Roofline, chip_peaks, model_flops,
                                   roofline_from_hlo)
from repro.configs import get_arch, SHAPES


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_flat_scan_flops_exact():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        y, _ = lax.scan(body, x, None, length=7)
        return y

    hc = analyze(_compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
                 .as_text(), 1)
    assert hc.dot_flops == 7 * 2 * 64 ** 3


def test_nested_scan_flops_exact():
    def g(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            c2, _ = lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = lax.scan(outer, x, None, length=5)
        return y

    hc = analyze(_compile(g, jax.ShapeDtypeStruct((32, 32), jnp.float32))
                 .as_text(), 1)
    assert hc.dot_flops == 5 * 3 * 2 * 32 ** 3


def test_unrolled_matches_scan():
    def unrolled(x):
        for _ in range(4):
            x = x @ x
        return x

    def scanned(x):
        def body(c, _):
            return c @ c, None
        y, _ = lax.scan(body, x, None, length=4)
        return y

    spec = jax.ShapeDtypeStruct((48, 48), jnp.float32)
    a = analyze(_compile(unrolled, spec).as_text(), 1)
    b = analyze(_compile(scanned, spec).as_text(), 1)
    assert a.dot_flops == b.dot_flops == 4 * 2 * 48 ** 3


def test_xla_cost_analysis_undercounts_loops():
    """Documents WHY hlo_costs exists: XLA's cost analysis counts scan
    bodies once."""
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = lax.scan(body, x, None, length=16)
        return y

    compiled = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    xla_flops = compiled.cost_analysis()["flops"]
    ours = analyze(compiled.as_text(), 1).dot_flops
    assert ours == 16 * 2 * 64 ** 3
    assert xla_flops < ours / 8          # massive undercount


def test_roofline_dominant_term():
    r = Roofline(compute_s=1.0, memory_s=2.0, collective_s=0.5,
                 flops_per_dev=1.0, bytes_per_dev=1.0, coll_bytes_per_dev=1.0,
                 model_flops=chip_peaks("TPU v5 lite").flops)
    assert r.dominant == "memory"
    assert r.bound_s == 2.0
    assert r.mfu_bound == pytest.approx(0.5)


def test_chip_peaks_table():
    """The v5e entry keeps the published numbers under both kind names;
    a kind without an entry is an error, not a default."""
    for kind in ("TPU v5 lite", "TPU v5e"):
        p = chip_peaks(kind)
        assert (p.flops, p.hbm_bw, p.ici_bw) == (197e12, 819e9, 50e9)
        assert p.source
    assert all(p.source for p in PEAKS.values())
    with pytest.raises(KeyError, match="cpu"):
        chip_peaks("cpu")


def test_model_flops_conventions():
    cfg = get_arch("deepseek-7b")
    n = cfg.active_param_count()
    assert model_flops(cfg, SHAPES["train_4k"]) == \
        pytest.approx(6.0 * n * 4096 * 256)
    assert model_flops(cfg, SHAPES["decode_32k"]) == \
        pytest.approx(2.0 * n * 128)
    moe = get_arch("deepseek-v3-671b")
    assert model_flops(moe, SHAPES["train_4k"]) < \
        6.0 * moe.param_count() * 4096 * 256  # active, not total
