"""deepseek-v2-lite — MLA + fine-grained MoE [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json].

27L, d_model=2048, 16 heads with Multi-head Latent Attention and no query
compression (kv_lora=512, nope/rope/v head dims 128/64/128), YaRN rope
(factor 40 over 4096 positions), vocab 102400, untied head.  MoE: 2 shared
+ 64 routed experts, top-6 by softmax with the gates left unnormalized,
expert d_ff=1408; the first layer uses a dense FFN (d_ff=10944).
"""
from repro.configs.base import ModelConfig, StageSpec, register

CONFIG = register(
    ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        head_dim=128,
        d_ff=10944,  # the single dense layer
        vocab_size=102400,
        stages=(
            StageSpec(kinds=("attn",), repeats=1, moe=(False,)),
            StageSpec(kinds=("attn",), repeats=26, moe=(True,)),
        ),
        rope_theta=10000.0,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_mscale_all_dim=0.707,
        kv_lora_rank=512,
        qk_rope_dim=64,
        moe_experts=64,
        moe_top_k=6,
        moe_shared_experts=2,
        moe_d_ff=1408,
        moe_norm_topk=False,
        mlp_kind="swiglu",
        norm_eps=1e-6,
        tie_embeddings=False,
        source="hf:deepseek-ai/DeepSeek-V2-Lite (arXiv:2405.04434)",
    )
)
