"""HBM planner (Crispy-for-meshes): ladder profiling, linear gate,
extrapolation accuracy against a ground-truth full compile."""
import pytest

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.hbm_planner import HBMPlanner, _reduced_depth
from repro.core.catalog import tpu_catalog
# Auto axes: the planner's sharding rules are written for them
from repro.launch.mesh import make_mesh

GiB = 1024 ** 3


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1, 1), ("data", "model"))


def _small_shape():
    import dataclasses
    return dataclasses.replace(SHAPES["train_4k"], seq_len=128,
                               global_batch=4)


def test_reduced_depth_respects_family_structure():
    z = get_arch("zamba2-7b")
    r = _reduced_depth(z, 13)
    assert r.n_layers % z.hybrid.period == 0
    v = get_arch("llama-3.2-vision-90b")
    r = _reduced_depth(v, 17)
    assert r.n_layers % v.cross_attn.period == 0


def test_planner_memory_linear_in_depth(mesh1):
    """Per-device compiled memory is linear in layer count — the premise
    that makes the paper's OLS+R2 gate transfer — and the extrapolation to
    a deeper model lands within 10% of the ground-truth compile."""
    cfg = get_arch("deepseek-7b").reduced(d_model=128, n_layers=24,
                                          vocab_size=512)
    run = RunConfig(attn_impl="full", remat="nothing",
                    compute_dtype="float32", microbatches=1)
    planner = HBMPlanner(leeway=0.0)
    shape = _small_shape()
    rep = planner.plan(cfg, shape, mesh1, run=run, anchor_layers=10,
                       select=False)
    assert rep.model.confident, f"R2={rep.model.r2}"
    truth = planner.profile_memory(cfg, shape, mesh1, run)
    pred = rep.predicted_per_dev_gib * GiB
    rel = abs(pred - truth) / truth
    assert rel < 0.10, f"extrapolation off by {rel:.2%}"


def test_planner_selects_feasible_config(mesh1):
    planner = HBMPlanner(leeway=0.0)
    sel = planner.select(requirement_gib=100.0, per_dev_gib_at_profile=1.0)
    assert sel.config.usable_mem_gib(planner.overhead) >= 100.0
    sel0 = planner.select(requirement_gib=0.0, per_dev_gib_at_profile=0.0)
    assert sel0.fell_back


def test_planner_per_chip_constraint():
    """A requirement that fits in aggregate but not per chip must push to a
    bigger slice or a bigger chip."""
    planner = HBMPlanner(leeway=0.0)
    sel = planner.select(requirement_gib=16 * 14.0, per_dev_gib_at_profile=0)
    c = sel.config
    assert c.usable_mem_gib(planner.overhead) >= 16 * 14.0
    assert (16 * 14.0) / c.scale_out <= c.node.mem_gib - planner.overhead
