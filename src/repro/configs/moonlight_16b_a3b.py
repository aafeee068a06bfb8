"""moonlight-16b-a3b [moe]: DeepSeek-V3 blocks at d_model 2048
(hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3).

27L d_model=2048 16H, MLA with no query latent (kv_lora_rank 512, qk
128 + 64 rope, v 128); the first layer dense (d_ff 11264), then 64 routed
experts of width 1408, 6 a token, and 2 shared; sigmoid scores with a
selection bias (noaux_tc, one group), gates normalised over the 6 and
scaled by 2.446; the sequence-wise balance term; vocab 163840, untied;
RoPE 50000; RMSNorm 1e-5.  The bias step (0.001) and balance weight
(0.0001) are DeepSeek-V3's (arXiv 2412.19437), not in the config.
"""

from .base import Block, MLAConfig, ModelConfig, MoEConfig

ARCH_ID = "moonlight-16b-a3b"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,                       # the dense layer's hidden dim
        vocab_size=163_840,
        blocks_prefix=(Block("mla", "dense"),),
        blocks_pattern=(Block("mla", "moe"),),
        moe=MoEConfig(
            n_experts=64, top_k=6, d_expert=1408, n_shared=2, d_shared=1408,
            capacity_factor=1.25, scoring="sigmoid", routed_scale=2.446,
            balance_alpha=1e-4, bias_gamma=1e-3,
        ),
        mla=MLAConfig(
            q_lora_rank=None, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        ),
        rope_theta=50_000.0,
        norm_eps=1e-5,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke",
        family="moe",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        vocab_size=512,
        blocks_prefix=(Block("mla", "dense"),),
        blocks_pattern=(Block("mla", "moe"),),
        # high capacity factor: no token drops -> decode/full-forward parity
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=2, d_shared=32,
                      capacity_factor=8.0, scoring="sigmoid", routed_scale=2.446,
                      balance_alpha=1e-4, bias_gamma=1e-3),
        mla=MLAConfig(
            q_lora_rank=None, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        ),
        rope_theta=50_000.0,
        norm_eps=1e-5,
    )
