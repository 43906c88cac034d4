"""Config system: model architectures, input shapes, and run plans.

Every assigned architecture is a :class:`ModelConfig` built from a repeating
**layer pattern** (a tuple of :class:`LayerSpec`), which is how heterogeneous
stacks (Jamba's 1-attention-per-8, Gemma2's local/global alternation,
MoE-every-other-layer) are expressed while still compiling as a single
``lax.scan`` over pattern repeats ("units").  ``n_layers`` must be a multiple
of ``len(pattern)``.

Shapes are the four assigned input-shape cells; ``kind`` selects which step
function a cell lowers (``train`` → train_step, ``prefill``/``decode`` →
serve steps).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

__all__ = [
    "MoESpec",
    "MLASpec",
    "MambaSpec",
    "RWKVSpec",
    "LayerSpec",
    "ModelConfig",
    "ShapeConfig",
    "SHAPES",
    "smoke_variant",
]


@dataclass(frozen=True)
class MoESpec:
    n_experts: int                # experts the router scores
    top_k: int
    d_ff: int                     # per-expert hidden width
    n_shared: int = 0             # always-on experts: one MLP of n_shared × d_ff
    router_jitter: float = 0.0
    load_balance_coef: float = 0.01
    capacity_factor: float = 1.25  # per-expert slots = ceil(S·K·cf/E)
    scoring: str = "softmax"      # 'softmax' | 'sigmoid'
    routed_scale: float = 1.0     # renormalised top-k weights × this
    balance: str = "switch"       # 'switch' (top-1, batch) | 'seq' (DeepSeek-V3, per row)
    bias_rate: float = 0.0        # > 0: a choice-only correction bias, b += rate·sign(mean load − load)
    n_held: int = 0               # experts held here (expert parallelism); 0 ⇒ all
    first_held: int = 0           # id of the first held expert

    def __post_init__(self):
        assert self.scoring in ("softmax", "sigmoid"), self.scoring
        assert self.balance in ("switch", "seq"), self.balance
        assert 0 <= self.first_held and self.first_held + self.held <= self.n_experts

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class MLASpec:
    """Multi-head latent attention (DeepSeek-V2/V3), without query compression."""

    kv_lora_rank: int             # width of the shared key/value latent
    qk_nope_head_dim: int
    qk_rope_head_dim: int         # rotary key dims, shared by every head
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class MambaSpec:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 ⇒ ceil(d_model/16)


@dataclass(frozen=True)
class RWKVSpec:
    head_dim: int = 64


@dataclass(frozen=True)
class LayerSpec:
    """One position in the repeating pattern unit."""

    mixer: str  # 'attn' | 'attn_local' | 'mla' | 'mamba' | 'rwkv'
    ffn: str    # 'dense' | 'moe' | 'rwkv_cmix'

    def __post_init__(self):
        assert self.mixer in ("attn", "attn_local", "mla", "mamba", "rwkv"), self.mixer
        assert self.ffn in ("dense", "moe", "rwkv_cmix"), self.ffn


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense|moe|hybrid|ssm|encdec|vlm|audio
    d_model: int
    n_layers: int
    pattern: Tuple[LayerSpec, ...]
    vocab_size: int
    lead: Tuple[LayerSpec, ...] = ()  # layers run once, before the repeated pattern
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0              # 0 ⇒ d_model // n_heads
    d_ff: int = 0
    activation: str = "swiglu"     # swiglu|gelu|relu2
    norm: str = "rmsnorm"          # rmsnorm|layernorm
    use_rope: bool = True
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0     # partial rotary (StableLM: 0.25)
    qkv_bias: bool = False
    qk_norm: bool = False          # OLMoE-style q/k RMSNorm
    attn_window: int = 0           # sliding window for 'attn_local' mixers
    attn_softcap: float = 0.0      # Gemma2 attention-logit softcap
    final_softcap: float = 0.0     # Gemma2 final-logit softcap
    post_block_norm: bool = False  # Gemma2 sandwich norms
    tie_embeddings: bool = True
    moe: Optional[MoESpec] = None
    mla: Optional[MLASpec] = None
    mamba: Optional[MambaSpec] = None
    rwkv: Optional[RWKVSpec] = None
    # encoder-decoder (Seamless backbone): n_layers is the decoder depth
    n_enc_layers: int = 0
    # modality frontend stubs ([audio]/[vlm]): precomputed embeddings
    frontend: Optional[str] = None  # None|'vision'|'audio'
    frontend_dim: int = 0           # embedding dim delivered by the stub
    frontend_tokens: int = 0        # patches/frames per example
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # performance knobs
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    ssm_chunk: int = 256
    scan_layers: bool = True
    remat: str = "unit"            # 'none'|'unit'|'dots'
    remat_loss_chunk: bool = False # recompute logits chunks in backward
    use_pallas: bool = False       # TPU deployment flag; CPU dry-run uses jnp path
    # capability flags
    sub_quadratic: bool = False    # eligible for long_500k

    def __post_init__(self):
        assert (self.n_layers - len(self.lead)) % len(self.pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} less {len(self.lead)} leading "
            f"layers not a multiple of pattern len {len(self.pattern)}"
        )
        layers = self.lead + self.pattern
        if any(s.mixer in ("attn", "attn_local", "mla") for s in layers):
            assert self.n_heads > 0 and self.n_kv_heads > 0
        if any(s.mixer == "mla" for s in layers):
            assert self.mla is not None
        if any(s.ffn == "moe" for s in layers):
            assert self.moe is not None
        if any(s.mixer == "mamba" for s in layers):
            assert self.mamba is not None
        if any(s.mixer == "rwkv" for s in layers):
            assert self.rwkv is not None

    # -- derived ---------------------------------------------------------------
    @property
    def n_units(self) -> int:
        return (self.n_layers - len(self.lead)) // len(self.pattern)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # 'train' | 'prefill' | 'decode'
    seq_len: int
    global_batch: int

    def __post_init__(self):
        assert self.kind in ("train", "prefill", "decode")


#: The assigned LM-transformer shape set (same four cells for every arch).
SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """A reduced same-family config for CPU smoke tests.

    Keeps the pattern (so every mixer/ffn kind is exercised) but shrinks
    width, depth, vocab and expert count.
    """
    kw: Dict = dict(
        d_model=64,
        n_layers=len(cfg.lead) + len(cfg.pattern),   # the leading layers and one unit
        d_ff=128,
        vocab_size=512,
        dtype="float32",
        param_dtype="float32",
        attn_chunk_q=32,
        attn_chunk_kv=32,
        ssm_chunk=16,
        remat="none",
    )
    if cfg.n_heads:
        kw["n_heads"] = 4
        kw["n_kv_heads"] = max(1, 4 * cfg.n_kv_heads // max(cfg.n_heads, 1))
        kw["head_dim"] = 16
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=min(cfg.moe.top_k, 2), d_ff=64, n_held=0, first_held=0,
        )
    if cfg.mla is not None:
        kw["mla"] = MLASpec(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    if cfg.mamba is not None:
        kw["mamba"] = MambaSpec(d_state=8, d_conv=4, expand=2)
    if cfg.rwkv is not None:
        kw["rwkv"] = RWKVSpec(head_dim=16)
    if cfg.n_enc_layers:
        kw["n_enc_layers"] = 1
    if cfg.frontend:
        kw["frontend_dim"] = 32
        kw["frontend_tokens"] = 8
    if cfg.attn_window:
        kw["attn_window"] = 16
    return cfg.replace(name=cfg.name + "-smoke", **kw)
