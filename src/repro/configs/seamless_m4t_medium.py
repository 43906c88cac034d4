"""seamless-m4t-medium — encoder-decoder multimodal backbone [arXiv:2308.11596].

Assigned: 12L, d_model=1024, 16H (GQA kv=16 ⇒ MHA), d_ff=4096, vocab=256206.
Per the assignment the modality frontend is a STUB: ``input_specs()``
supplies precomputed speech-frame embeddings (frontend_dim=1024) and the
backbone is the 12L encoder + 12L decoder transformer with cross-attention.
Full attention ⇒ long_500k is skipped;
decode shapes lower the enc-dec serve step (this is NOT encoder-only).
"""

from .base import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="seamless-m4t-medium",
    family="audio",
    d_model=1024,
    n_layers=12,          # decoder depth
    n_enc_layers=12,
    pattern=(LayerSpec(mixer="attn", ffn="dense"),),
    vocab_size=256206,
    n_heads=16,
    n_kv_heads=16,
    head_dim=64,
    d_ff=4096,
    activation="gelu",
    norm="layernorm",
    frontend="audio",
    frontend_dim=1024,
)
