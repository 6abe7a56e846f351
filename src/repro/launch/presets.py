"""Per-(arch x shape x mesh) RunConfig presets — the baseline points the
perf hillclimb starts from. Tuned for v5e (16 GiB HBM/chip):

* microbatches sized so each device sees ~1 sequence per microbatch at
  train_4k (activation stash = n_layers * S * d * 2B per device with
  remat='boundaries');
* FSDP (2D weight sharding over data x model) for >=30B-param archs —
  a 123B bf16 replica over only the model axis would be 15.4 GiB/chip;
* expert FSDP for deepseek-v3 (652B expert params need sharding over both
  axes: 256 experts / 16 model-shards x ff/16 over data);
* decode/prefill run microbatches=1 and keep ZeRO off (no optimizer).

A model is big by its published depth, not by the depth it runs at: a
registered architecture cut to fewer layers (one pipeline stage of it)
keeps the storage its whole model gets.
"""
from __future__ import annotations

import dataclasses

from repro.configs import ARCHS
from repro.configs.base import MeshConfig, ModelConfig, RunConfig, ShapeConfig

_BIG_PARAMS = 30e9


def published_params(cfg: ModelConfig) -> int:
    """Parameters of `cfg` at the depth its registered architecture
    publishes (its own depth when it is not registered)."""
    arch = ARCHS.get(cfg.name)
    if arch is not None and arch.n_layers != cfg.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=arch.n_layers)
    return cfg.param_count()


def preset_run(cfg: ModelConfig, shape: ShapeConfig,
               mesh_cfg: MeshConfig) -> RunConfig:
    big = published_params(cfg) >= _BIG_PARAMS
    run = RunConfig(
        attn_impl="blocked",
        remat="boundaries",
        compute_dtype="bfloat16",
        param_dtype="bfloat16" if big else "float32",
        moment_dtype="bfloat16" if big else "float32",
        fsdp_params=big,
        fsdp_experts=(cfg.moe is not None and cfg.moe.n_experts >= 128),
        zero1=True,
    )
    if shape.mode == "train":
        dp = mesh_cfg.dp
        mb = max(1, shape.global_batch // dp)
        # small models can afford 2 seqs per microbatch
        if cfg.d_model < 4096 and mb % 2 == 0:
            mb //= 2
        run = run.with_(microbatches=mb)
    else:
        run = run.with_(microbatches=1, zero1=False, remat="nothing")
    if shape.seq_len >= 32768:
        run = run.with_(attn_block_q=1024, attn_block_kv=2048)
    return run
