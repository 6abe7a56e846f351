"""Architecture registry: --arch <id> resolves here."""
from repro.configs.base import (ModelConfig, MoEConfig, MLAConfig, SSMConfig,
                                YarnConfig,
                                HybridConfig, EncDecConfig, CrossAttnConfig,
                                ShapeConfig, MeshConfig, RunConfig,
                                SHAPES, TRAIN_4K, PREFILL_32K, DECODE_32K,
                                LONG_500K, SINGLE_POD, MULTI_POD, cell_id)

from repro.configs.deepseek_v3_671b import CONFIG as _dsv3, EP32 as _dsv3_ep32
from repro.configs.olmoe_1b_7b import CONFIG as _olmoe
from repro.configs.zamba2_7b import CONFIG as _zamba2
from repro.configs.mistral_large_123b import CONFIG as _mistral
from repro.configs.deepseek_7b import CONFIG as _ds7b
from repro.configs.nemotron_4_15b import CONFIG as _nemotron
from repro.configs.chatglm3_6b import CONFIG as _chatglm
from repro.configs.rwkv6_7b import CONFIG as _rwkv
from repro.configs.llama32_vision_90b import CONFIG as _llamav
from repro.configs.whisper_small import CONFIG as _whisper

ARCHS = {c.name: c for c in (
    _dsv3, _dsv3_ep32, _olmoe, _zamba2, _mistral, _ds7b,
    _nemotron, _chatglm, _rwkv, _llamav, _whisper)}


def get_arch(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    """DESIGN.md §4 grid skips: long_500k only for sub-quadratic archs."""
    if shape.name == "long_500k":
        return cfg.sub_quadratic
    return True


def grid(include_skipped: bool = False):
    """All (arch, shape) cells of the assigned grid."""
    for name, cfg in ARCHS.items():
        for shape in SHAPES.values():
            if include_skipped or shape_applicable(cfg, shape):
                yield cfg, shape
