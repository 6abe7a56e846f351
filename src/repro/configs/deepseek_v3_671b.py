"""deepseek-v3-671b [moe] — MLA, 1 shared + 256 routed top-8 experts, MTP.
[arXiv:2412.19437; hf]

The router is published as a sigmoid scorer whose selection adds
`e_score_correction_bias`, limited to the best `topk_group` of `n_group`
expert groups (a group scores the sum of its two best biased scores),
with the eight gates renormalised to 1 and scaled by 2.5. Rope is YaRN
(factor 40 over 4,096 original positions), which also sharpens the MLA
softmax by mscale^2.

`EP32` is one chip's share of the model as served with expert
parallelism over 32 chips: experts 0-7 of every MoE layer (the router
still scores all 256) and an eighth of the vocabulary; attention, the
shared expert and the router are replicated. It decodes one token a step,
so it carries no MTP module.
"""
import dataclasses

from repro.configs.base import ModelConfig, MoEConfig, MLAConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,            # MLA: heads share a compressed latent, not GQA
    d_ff=2048,                 # per-expert ff (spec); dense layers use d_ff_dense
    vocab_size=129280,
    attention_kind="mla",
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=256, top_k=8, d_ff_expert=2048,
                  n_shared_experts=1, first_dense_layers=3, d_ff_dense=18432,
                  impl="ep_tp", scoring="sigmoid", n_group=8, topk_group=4,
                  norm_topk_prob=True, routed_scaling_factor=2.5),
    mtp_depth=1,
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    norm_eps=1e-6,
    source="[arXiv:2412.19437; hf]",
)

EP32 = dataclasses.replace(
    CONFIG,
    name="deepseek-v3-671b-ep32",
    moe=dataclasses.replace(CONFIG.moe, n_held=8, first_held=0),
    vocab_size=129280 // 8,
    mtp_depth=0,
)
