"""HBM planner as the pipeline's profile source: ladder depths, aggregate
bytes, extrapolation accuracy against a ground-truth full compile through
`AllocationPipeline`, and TPU-catalog selection."""
import pytest

from repro.configs import SHAPES, get_arch
from repro.configs.base import RunConfig
from repro.core.catalog import tpu_catalog
from repro.core.hbm_planner import (HBMPlanner, TPU_OVERHEAD_GIB,
                                    _reduced_depth)
from repro.core.history import ExecutionHistory
from repro.core.selector import select_crispy
# Auto axes: the planner's sharding rules are written for them
from repro.launch.mesh import make_mesh
from repro.pipeline import AllocationPipeline, PipelineRequest


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


@pytest.mark.parametrize("arch,depths", [
    ("deepseek-7b", [2, 3, 4, 6, 7]),
    ("zamba2-7b", [6, 12, 18]),
    ("llama-3.2-vision-90b", [5, 10, 15]),
])
def test_ladder_depths_are_compiled_depths(arch, depths):
    """The ladder is the distinct depths `_reduced_depth` compiles, so the
    pipeline fits bytes against the depth that made them."""
    cfg = get_arch(arch)
    ladder = HBMPlanner.ladder(cfg)
    assert ladder == depths
    assert [_reduced_depth(cfg, d).n_layers for d in ladder] == ladder


def test_planner_memory_linear_in_depth(mesh1):
    """Per-device compiled memory is linear in layer count — the premise
    that makes the paper's OLS+R2 gate transfer — and the extrapolation to
    a deeper model lands within 10% of the ground-truth compile."""
    cfg = get_arch("deepseek-7b").reduced(d_model=128, n_layers=24,
                                          vocab_size=512)
    run = RunConfig(attn_impl="full", remat="nothing",
                    compute_dtype="float32", microbatches=1)
    planner = HBMPlanner()
    shape = _small_shape()
    tr = AllocationPipeline(tpu_catalog(), ExecutionHistory(),
                            overhead_per_node_gib=TPU_OVERHEAD_GIB).run(
        PipelineRequest("linear", planner.profile_at(cfg, shape, mesh1, run),
                        full_size=cfg.n_layers,
                        sizes=HBMPlanner.ladder(cfg, 10)))
    assert tr.plan.fit.confident, f"R2={tr.plan.fit.r2}"
    truth = planner.profile_memory(cfg, shape, mesh1, run)
    pred = tr.requirement_gib * 1024 ** 3
    rel = abs(pred - truth) / truth
    assert rel < 0.10, f"extrapolation off by {rel:.2%}"


def test_profile_at_reports_aggregate_bytes(mesh1):
    """On a one-device mesh the aggregate is `profile_memory`'s number;
    the point carries the size it was asked for and its wall time."""
    cfg = get_arch("deepseek-7b").reduced(d_model=64, n_layers=8,
                                          vocab_size=256)
    run = RunConfig(attn_impl="full", remat="nothing",
                    compute_dtype="float32", microbatches=1)
    planner = HBMPlanner()
    shape = _small_shape()
    r = planner.profile_at(cfg, shape, mesh1, run)(3.0)
    assert r.size == 3.0 and r.wall_s > 0.0
    assert r.job_mem_bytes == planner.profile_memory(
        _reduced_depth(cfg, 3), shape, mesh1, run)


def test_planner_contains_no_decision_logic():
    """hbm_planner.py is a profile source ONLY: the fit and the selection
    are the pipeline's (the structural twin of
    test_service_contains_no_pipeline_logic)."""
    import repro.core.hbm_planner as planner_mod
    src = open(planner_mod.__file__).read()
    hits = [w for w in ("select_bfa", "fit_memory_model", "usable_mem_gib")
            if w in src]
    assert not hits, f"hbm_planner.py re-grew decision logic: {hits}"


def test_planner_selects_feasible_config():
    sel = select_crispy(tpu_catalog(), ExecutionHistory(), 100.0,
                        overhead_per_node_gib=TPU_OVERHEAD_GIB)
    assert sel.config.usable_mem_gib(TPU_OVERHEAD_GIB) >= 100.0
    sel0 = select_crispy(tpu_catalog(), ExecutionHistory(), 0.0,
                         overhead_per_node_gib=TPU_OVERHEAD_GIB)
    assert sel0.fell_back


def test_planner_per_chip_constraint():
    """A requirement that fits in aggregate but not per chip must push to a
    bigger slice or a bigger chip."""
    sel = select_crispy(tpu_catalog(), ExecutionHistory(), 16 * 14.0,
                        overhead_per_node_gib=TPU_OVERHEAD_GIB)
    c = sel.config
    assert c.usable_mem_gib(TPU_OVERHEAD_GIB) >= 16 * 14.0
    assert (16 * 14.0) / c.scale_out <= c.node.mem_gib - TPU_OVERHEAD_GIB
