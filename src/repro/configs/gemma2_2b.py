"""gemma2-2b — dense with local/global alternating attention [arXiv:2408.00118].

Assigned: 26L, d_model=2304, 8H (GQA kv=4), d_ff=9216, vocab=256000.
Gemma2 signature: alternating 4096-token sliding-window and global layers,
attention-logit softcap 50, final-logit softcap 30, sandwich (post-block)
RMSNorms, GeGLU MLP, head_dim=256, tied embeddings scaled by sqrt(d_model).

long_500k runs: half the layers are sliding-window (ring KV cache of 4096);
the global layers decode O(S) against their cache.
"""

from .base import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="gemma2-2b",
    family="dense",
    d_model=2304,
    n_layers=26,
    pattern=(
        LayerSpec(mixer="attn_local", ffn="dense"),
        LayerSpec(mixer="attn", ffn="dense"),
    ),
    vocab_size=256000,
    n_heads=8,
    n_kv_heads=4,
    head_dim=256,
    d_ff=9216,
    activation="gelu",
    norm="rmsnorm",
    attn_window=4096,
    attn_softcap=50.0,
    final_softcap=30.0,
    post_block_norm=True,
    tie_embeddings=True,
    sub_quadratic=True,   # windowed layers bound the quadratic term
)
