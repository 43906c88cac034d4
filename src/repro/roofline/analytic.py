"""Closed-form FLOPs / HBM-bytes model per (arch × shape) cell.

Why analytic: XLA's ``cost_analysis`` counts a while-loop body **once**
(demonstrated in tests/test_roofline.py), so any scan-based program — unit
scans, microbatch accumulation, chunked attention — is undercounted by its
trip counts.  Collectives are corrected per-region
(:mod:`repro.roofline.hlo_loops`); compute and memory use the closed forms
below, cross-validated against cost_analysis on single-unit unrolled
lowerings (test_roofline.py::test_analytic_matches_unrolled_cost).

All formulas are per **forward** token unless stated; train multiplies by 3
(backward ≈ 2× forward).  MACs count as 2 FLOPs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

__all__ = ["cell_flops", "cell_bytes", "flops_breakdown"]


def _attn_layer_flops(cfg, S: int, T_ctx: float, *, decode: bool) -> float:
    """One attention layer, per token.  T_ctx = average keys attended."""
    D = cfg.d_model
    hd = cfg.resolved_head_dim
    H, Kv = cfg.n_heads, cfg.n_kv_heads
    proj = 2 * D * (H * hd) + 2 * D * (Kv * hd) * 2 + 2 * (H * hd) * D
    scores = 2 * H * hd * T_ctx * 2  # QK^T and PV
    return proj + scores


def _mla_layer_flops(cfg, T_ctx: float) -> float:
    """MLA per token: query, latent, key/value and output projections, then
    scores over nope+rope and values over v per head."""
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    proj = 2 * D * H * m.qk_head_dim + 2 * D * (m.kv_lora_rank + m.qk_rope_head_dim)
    proj += 2 * m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim) + 2 * H * m.v_head_dim * D
    return proj + 2 * H * (m.qk_head_dim + m.v_head_dim) * T_ctx


def _mlp_flops(cfg, d_ff: int) -> float:
    n_mat = 3 if cfg.activation == "swiglu" else 2
    return n_mat * 2 * cfg.d_model * d_ff


def _moe_layer_flops(cfg, S: int, *, capacity_factor: float = 1.25) -> float:
    """MoE FFN per token: router + dense one-hot dispatch/combine + experts.

    The dispatch einsums cost 2·(E·C)·D per token with E·C ≈ S·K·cf —
    linear in the sequence length.
    """
    spec = cfg.moe
    D = cfg.d_model
    E, K, F = spec.n_experts, spec.top_k, spec.d_ff
    EC = S * K * capacity_factor
    router = 2 * D * E
    dispatch = 2 * EC * D * 2  # dispatch + combine
    n_mat = 3 if cfg.activation == "swiglu" else 2
    experts = K * capacity_factor * n_mat * 2 * D * F
    shared = _mlp_flops(cfg, spec.n_shared * F)
    return router + dispatch + experts + shared


def _mamba_layer_flops(cfg) -> float:
    D = cfg.d_model
    m = cfg.mamba
    di = m.expand * D
    dr = m.dt_rank or max(1, math.ceil(D / 16))
    ds = m.d_state
    proj = 2 * D * 2 * di + 2 * di * (dr + 2 * ds) + 2 * dr * di + 2 * di * D
    conv = 2 * m.d_conv * di
    scan = 8 * di * ds  # decay/drive/update/readout elementwise + reduce
    return proj + conv + scan


def _rwkv_tmix_flops(cfg, chunk: int) -> float:
    D = cfg.d_model
    C = cfg.rwkv.head_dim
    H = D // C
    r = min(64, D)
    proj = 5 * 2 * D * D + 2 * D * r + 2 * r * D  # r,k,v,g,o + decay LoRA
    # chunked WKV per token: inter/state 2·(2·H·C²) + intra ≈ 4·chunk·H·C
    wkv = 4 * H * C * C + 4 * chunk * H * C
    return proj + wkv


def _rwkv_cmix_flops(cfg) -> float:
    return 2 * cfg.d_model * cfg.d_ff * 2 + 2 * cfg.d_model * cfg.d_model


def flops_breakdown(cfg, shape) -> Dict[str, float]:
    """Per-token forward FLOPs by component (whole stack)."""
    S = shape.seq_len
    decode = shape.kind == "decode"
    out: Dict[str, float] = {"mixer": 0.0, "ffn": 0.0, "unembed": 0.0}
    # average context per query token
    if decode:
        T_full = float(S)
    else:
        T_full = (S + 1) / 2.0  # causal average
    layers = [(spec, 1) for spec in cfg.lead] + [(spec, cfg.n_units) for spec in cfg.pattern]
    for spec, n in layers:
        if spec.mixer == "mla":
            out["mixer"] += n * _mla_layer_flops(cfg, T_full)
        elif spec.mixer in ("attn", "attn_local"):
            T_ctx = T_full
            if spec.mixer == "attn_local" and cfg.attn_window:
                T_ctx = min(T_full, float(cfg.attn_window))
            out["mixer"] += n * _attn_layer_flops(cfg, S, T_ctx, decode=decode)
        elif spec.mixer == "mamba":
            out["mixer"] += n * _mamba_layer_flops(cfg)
        elif spec.mixer == "rwkv":
            out["mixer"] += n * _rwkv_tmix_flops(cfg, min(cfg.ssm_chunk, S))
        if spec.ffn == "dense":
            out["ffn"] += n * _mlp_flops(cfg, cfg.d_ff)
        elif spec.ffn == "moe":
            out["ffn"] += n * _moe_layer_flops(cfg, S)
        elif spec.ffn == "rwkv_cmix":
            out["ffn"] += n * _rwkv_cmix_flops(cfg)
    if cfg.is_encdec:
        # encoder (bidirectional, enc_len = S/4) + decoder cross-attention
        from repro.models.encdec import enc_len_for

        Se = enc_len_for(cfg, S)
        enc_per_tok = cfg.n_enc_layers * (
            _attn_layer_flops(cfg, Se, float(Se), decode=False) + _mlp_flops(cfg, cfg.d_ff)
        )
        out["encoder"] = enc_per_tok * (Se / max(S, 1))  # normalized per decoder token
        out["mixer"] += cfg.n_layers * _attn_layer_flops(
            cfg, S, float(Se), decode=decode
        )  # cross-attn
    out["unembed"] = 2 * cfg.d_model * cfg.vocab_size
    return out


def cell_flops(cfg, shape) -> float:
    """Total fleet FLOPs for one step of this cell."""
    per_tok = sum(flops_breakdown(cfg, shape).values())
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 3.0 * per_tok * tokens  # fwd + bwd(2×)
    if shape.kind == "prefill":
        return per_tok * shape.global_batch * shape.seq_len
    return per_tok * shape.global_batch  # decode: one token per row


def cell_bytes(cfg, shape, *, n_params: int, n_devices: int, fsdp: bool, tp: int = 16) -> float:
    """Per-chip HBM traffic for one step (napkin model, documented):

    train  : optimizer state r/w (10 passes × 4B × N / state_shards)
             + activation traffic (~12 × local_tokens × D × 2B × L)
    prefill: params read (2B × N / tp) + activation traffic (fwd only)
    decode : params read + KV-cache read per token
    """
    D, L = cfg.d_model, cfg.n_layers
    state_shards = n_devices if fsdp else tp
    if shape.kind == "train":
        local_tokens = shape.global_batch * shape.seq_len / (n_devices / tp)
        state = 10.0 * 4 * n_params / state_shards
        acts = 12.0 * local_tokens * D * 2 * L / tp
        return state + acts
    if shape.kind == "prefill":
        local_tokens = shape.global_batch * shape.seq_len / (n_devices / tp)
        return 2.0 * n_params / state_shards + 4.0 * local_tokens * D * 2 * L / tp
    # decode
    hd = cfg.resolved_head_dim
    n_attn = sum(cfg.n_units for s in cfg.pattern if s.mixer in ("attn", "attn_local"))
    cache = 2 * 2 * shape.seq_len * cfg.n_kv_heads * hd * n_attn  # bf16 k+v
    local_rows = max(shape.global_batch / (n_devices / tp), 1)
    return 2.0 * n_params / state_shards + cache * local_rows / tp
