"""Model FLOPs of one training step of a DeepSeek-V3-style configuration.

As :mod:`chipbench.flops`: forward plus backward (3 × forward),
multiply-adds as 2 FLOPs, causal attention over the (S + 1) / 2 keys a query
sees on average.  MLA: the query, latent, key/value and output projections
and the scores over (nope + rope) and values over v per head.  An MoE layer:
its router over all experts, the shared MLP, and of each token's
``num_experts_per_tok`` choices only the share bound for the experts held
here (K × held / all experts a token).  Not counted: dispatch and combine,
capacity padding, rematerialised forwards, norms and softmaxes.
"""

from __future__ import annotations

from typing import Any, Dict


def forward_per_token(c: Dict[str, Any], seq_len: int) -> float:
    D, H = c["hidden_size"], c["num_attention_heads"]
    R, nope, rope, dv = c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    proj = 2 * D * H * (nope + rope) + 2 * D * (R + rope) + 2 * R * H * (nope + dv) + 2 * H * dv * D
    scores = 2 * H * (nope + rope + dv) * (seq_len + 1) / 2   # QK^T and PV, causal
    mla = proj + scores
    gated = lambda width: 3 * 2 * D * width                    # SwiGLU: gate, up, down
    E = c["n_routed_experts"] * c["ep_size"]
    held_share = c["num_experts_per_tok"] * c["n_routed_experts"] / E
    moe = 2 * D * E + held_share * gated(c["moe_intermediate_size"]) \
        + gated(c["n_shared_experts"] * c["moe_intermediate_size"])
    n_lead = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_lead
    head = 2 * D * c["vocab_size"]
    return c["num_hidden_layers"] * mla + n_lead * gated(c["intermediate_size"]) + n_moe * moe + head


def train_step(c: Dict[str, Any], batch: int, seq_len: int) -> float:
    return 3.0 * forward_per_token(c, seq_len) * batch * seq_len
