"""deepseek-v2-lite [moe, mla] — 27L d_model=2048 16H, multi-head latent
attention (kv_lora_rank 512, q heads of 128 nope + 64 rope, v 128, no
q-LoRA), YaRN rope (factor 40 over 4096), layer 0 dense (ff 10944),
layers 1-26 MoE: 64 routed experts of 1408, softmax top-6 not
renormalised, 2 shared experts (one gated MLP of 2816), vocab 102400,
untied [hf:deepseek-ai/DeepSeek-V2-Lite config.json].

``full()`` is what one chip of a four-chip expert-parallel host holds:
every published width and layer, the router's 64 outputs and top-6,
and routed experts 0-15 (``experts_held``).  ``replace(experts_held=
None)`` holds all 64 (15.7 B parameters: more than a chip holds)."""

from repro.configs.base import ModelConfig

ARCH = "deepseek-v2-lite"


def full() -> ModelConfig:
    return ModelConfig(
        name=ARCH, family="decoder",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        head_dim=192, d_ff=1408, vocab_size=102400,
        norm="rmsnorm", activation="silu", gated_mlp=True,
        rope_theta=10000.0,
        num_experts=64, experts_per_token=6, moe_impl="dropless",
        norm_topk_prob=False, shared_experts_width=2816,
        experts_held=(0, 16), first_dense_layers=1, dense_d_ff=10944,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        yarn_factor=40.0, yarn_original_max_position=4096,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
    )


def tiny() -> ModelConfig:
    return full().replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=24,
        d_ff=32, vocab_size=512, num_experts=8, experts_per_token=3,
        shared_experts_width=64, experts_held=(0, 4), dense_d_ff=96,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, remat="none",
    )
