"""llama4-maverick-400b-a17b — MoE 128e top-1 with shared expert
[hf:meta-llama/Llama-4-Scout-17B-16E].

Assigned (literal, treated as source of truth):
48L, d_model=5120, 40H (GQA kv=8), d_ff=8192 per expert, vocab=202048,
MoE 128 experts top-1 + an always-on shared expert (Llama4 signature).
"""

from .base import LayerSpec, ModelConfig, MoESpec

CONFIG = ModelConfig(
    name="llama4-maverick-400b-a17b",
    family="moe",
    d_model=5120,
    n_layers=48,
    pattern=(LayerSpec(mixer="attn", ffn="moe"),),
    vocab_size=202048,
    n_heads=40,
    n_kv_heads=8,
    head_dim=128,
    d_ff=8192,
    activation="swiglu",
    norm="rmsnorm",
    rope_theta=500_000.0,
    moe=MoESpec(n_experts=128, top_k=1, d_ff=8192, n_shared=1),
    tie_embeddings=False,
)
