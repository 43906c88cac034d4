"""moonlight-16b-a3b — DeepSeek-V3 architecture at 16B/3B active
[hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

Published: 27L, d_model=2048, 16H; multi-head latent attention with a
512-wide key/value latent, no query compression, q/k heads of 128 + 64
rotary dims (the rotary key shared by all heads), value heads of 128;
layer 0 a dense SwiGLU of 11264, layers 1-26 MoE: 64 routed experts of
1408, top-6, sigmoid scores with a choice-only correction bias (noaux_tc),
renormalised weights × 2.446, 2 shared experts; vocab=163840,
rope_theta=50000, untied head.
"""

from .base import LayerSpec, MLASpec, ModelConfig, MoESpec

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    d_model=2048,
    n_layers=27,
    lead=(LayerSpec(mixer="mla", ffn="dense"),),
    pattern=(LayerSpec(mixer="mla", ffn="moe"),),
    vocab_size=163840,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    activation="swiglu",
    norm="rmsnorm",
    rope_theta=50_000.0,
    mla=MLASpec(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoESpec(
        n_experts=64,
        top_k=6,
        d_ff=1408,
        n_shared=2,
        scoring="sigmoid",
        routed_scale=2.446,
        balance="seq",
        load_balance_coef=0.001,
        bias_rate=0.001,
    ),
    tie_embeddings=False,
    # at 8k tokens a row the loss chunks' f32 logits, kept for the backward,
    # would hold B·S·V·4 bytes; recompute them instead
    remat_loss_chunk=True,
)
