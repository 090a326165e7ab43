"""deepseek-v2-lite [moe] — hf:deepseek-ai/DeepSeek-V2-Lite (config.json).

27L, d_model=2048, vocab=102400, untied.  Attention is multi-head latent
attention (MLA) without a q low-rank: 16 heads of 128 no-rope + 64 rope
query/key dims, keys and values up-projected from a 512-wide RMS-normed
latent, one 64-dim rope key shared by every head, values 128 wide; YaRN
rope (factor 40 over 4096 original positions).  The first layer has a dense
SwiGLU MLP (d_ff 10944); the other 26 have 64 routed experts of width 1408
(softmax router, greedy top-6, no renormalisation) plus 2 shared experts as
one ungated SwiGLU MLP of width 2816; the routed gates are not scaled
(``routed_scaling_factor`` 1).  The router's load-balance weight is not in
the published config; 0.001 is assumed.
"""

from repro.configs.base import ModelConfig, Yarn, register


@register("deepseek-v2-lite")
def deepseek_v2_lite() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        d_ff=10944,
        vocab_size=102_400,
        block_pattern=("global",),
        act="silu",
        gated_mlp=True,
        norm_eps=1e-6,
        tie_embeddings=False,
        rope_theta=10_000.0,
        kv_lora_rank=512,
        qk_rope_head_dim=64,
        v_head_dim=128,
        yarn=Yarn(factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        first_k_dense_replace=1,
        num_experts=64,
        experts_held=64,
        top_k=6,
        d_ff_expert=1408,
        shared_expert_ff=2 * 1408,
        shared_expert_gate=False,
        norm_topk_prob=False,
        router_aux_coef=0.001,
    )
