"""Model FLOPs of one training step, from a configuration file's sizes.

What the model needs, not what the program executes: forward plus backward
(3 × forward), multiply-adds as 2 FLOPs, causal attention over the
(S + 1) / 2 keys a query sees on average, and for an MoE layer the router
and the ``num_experts_per_tok`` experts each token is routed to.  Not
counted: the one-hot dispatch and combine einsums, capacity padding,
rematerialised forwards, norms and softmaxes.
"""

from __future__ import annotations

from typing import Any, Dict


def forward_per_token(c: Dict[str, Any], seq_len: int) -> float:
    D, hd = c["hidden_size"], c["head_dim"]
    H, Kv = c["num_attention_heads"], c["num_key_value_heads"]
    proj = 2 * D * hd * (2 * H + 2 * Kv)           # q, k, v and the output projection
    scores = 2 * 2 * H * hd * (seq_len + 1) / 2    # QK^T and PV, causal
    gated = 3 * 2 * D * c["intermediate_size"]     # SwiGLU: gate, up, down
    if c.get("num_experts"):
        ffn = 2 * D * c["num_experts"] + c["num_experts_per_tok"] * gated
    else:
        ffn = gated
    head = 2 * D * c["vocab_size"]
    return c["num_hidden_layers"] * (proj + scores + ffn) + head


def train_step(c: Dict[str, Any], batch: int, seq_len: int) -> float:
    return 3.0 * forward_per_token(c, seq_len) * batch * seq_len
