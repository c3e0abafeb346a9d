"""DeepSeek-V2-Lite (15.7B, 2.4B active) [hf:deepseek-ai/DeepSeek-V2-Lite]
— MLA (kv_lora 512) with a direct query projection (no q-LoRA) and YaRN
rotary scaling, 64 routed experts top-6 by softmax without renormalising
the top-k, 2 shared experts, a dense first layer (d_ff 10944), and the
per-sequence balance loss (``seq_aux``; its weight 0.001 is the published
``aux_loss_alpha``)."""
import dataclasses

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,              # MLA: per-head K/V expanded from the latent
    d_ff=10944,                   # dense first layer
    vocab_size=102400,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, d_ff_shared=1408,
                  router_aux_weight=0.001, layer_pattern="all_but_first",
                  norm_topk_prob=False, routed_scaling_factor=1.0,
                  seq_aux=True),
    supports_long_context=False,
    long_context_skip_reason=(
        "MLA latent KV has no head axis to shard; blockwise latent-sharded "
        "attention is future work (DESIGN.md §4)"),
)


def smoke_config() -> ArchConfig:
    """Three layers (the dense one and two MoE), holding 2 of 4 routed
    experts, so the smoke tests run the held-share grouped-matmul path."""
    return dataclasses.replace(
        CONFIG, name="deepseek-lite-smoke", num_layers=3, d_model=128,
        num_heads=4, num_kv_heads=4, d_ff=256, vocab_size=512,
        mla=dataclasses.replace(CONFIG.mla, kv_lora_rank=32,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16),
        moe=dataclasses.replace(CONFIG.moe, num_experts=4, top_k=2,
                                d_ff_expert=64, d_ff_shared=64,
                                num_held_experts=2))
