"""DeepSeek-V2-Lite [arXiv:2405.04434; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite config.json]: 27L, d=2048, 16 heads of multi-head latent
attention (no q LoRA; a 512-wide KV latent plus a 64-wide shared rope key;
per head 128 nope + 64 rope query/key dims and 128 value dims), layer 0 a
dense SwiGLU of 10,944, layers 1-26 DeepSeekMoE: 64 routed experts of 1,408
(softmax, greedy top-6, weights not renormalised) plus 2 shared experts
merged as one SwiGLU of 2,816.  Vocab 102,400, untied head, RMSNorm eps
1e-6, RoPE theta 1e4 with DeepSeek yarn (factor 40 over 4,096 positions).

A decode step reads one 576-wide latent row per cached token and layer
(``blocks.mla_pool_rows``), not per-head keys and values: the engine's
second cache geometry (DESIGN.md §19)."""

from .base import ModelConfig

YARN = (40.0, 4096, 32.0, 1.0, 0.707, 0.707)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,            # qk_nope + qk_rope: the score's width
        d_ff=10944,
        vocab_size=102400,
        num_experts=64,
        top_k=6,
        moe_d_ff=1408,
        num_shared_experts=2,
        first_k_dense=1,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        rope_yarn=YARN,
        norm_eps=1e-6,
        head_pad_multiple=1,
        moe_sharding="tp",
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke", family="moe", num_layers=3,
        d_model=48, num_heads=4, num_kv_heads=4, head_dim=24, d_ff=96,
        vocab_size=163, num_experts=8, top_k=3, moe_d_ff=16,
        num_shared_experts=2, first_k_dense=1, norm_topk_prob=False,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=10000.0, rope_yarn=(4.0, 16, 32.0, 1.0,
                                                      0.707, 0.707),
        norm_eps=1e-6, head_pad_multiple=1, vocab_pad_multiple=16,
        attn_chunk=16, moe_sharding="tp", capacity_factor=4.0,
        compute_dtype="float32",
        remat="none",
    )
