"""Attention: GQA projections + chunked (flash-style) attention in pure JAX.

The forward pass never materializes the [S, T] score matrix: it is doubly
blocked (outer loop over query chunks, ``lax.scan`` over KV chunks) with the
standard running-max/running-sum online softmax.  This is the mathematical
twin of the Pallas TPU kernel in :mod:`repro.kernels.flash_attention`; the
model dispatches to the kernel when ``cfg.use_pallas`` is set and to this
implementation otherwise (CPU dry-runs, correctness oracles).

Supports: causal and bidirectional attention, sliding-window masks (Gemma2
local layers), attention-logit softcapping, GQA with arbitrary group counts,
partial-fraction RoPE, and single-token decode against a KV cache (the decode
formulation is context-parallel friendly: reductions over the KV axis lower
to collectives when the cache is sequence-sharded).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, apply_rope, dense, init_dense, init_norm, norm, rope_freqs, softcap

__all__ = [
    "init_attention",
    "flash_attention",
    "attention_layer",
    "decode_attention_layer",
    "init_kv_cache",
]

_BIG_NEG = -1e30


def init_attention(key, cfg, *, param_dtype) -> Params:
    """cfg: ModelConfig-like (d_model, n_heads, n_kv_heads, head_dim, ...)."""
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {
        "wq": init_dense(k1, cfg.d_model, (cfg.n_heads, hd), bias=cfg.qkv_bias, param_dtype=param_dtype),
        "wk": init_dense(k2, cfg.d_model, (cfg.n_kv_heads, hd), bias=cfg.qkv_bias, param_dtype=param_dtype),
        "wv": init_dense(k3, cfg.d_model, (cfg.n_kv_heads, hd), bias=cfg.qkv_bias, param_dtype=param_dtype),
        "wo": {
            "w": (
                jax.random.normal(k4, (cfg.n_heads, hd, cfg.d_model), dtype=jnp.float32)
                / math.sqrt(cfg.n_heads * hd)
            ).astype(param_dtype)
        },
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm("rmsnorm", hd, param_dtype=param_dtype)
        p["k_norm"] = init_norm("rmsnorm", hd, param_dtype=param_dtype)
    return p


# ---------------------------------------------------------------------------
# chunked flash attention (the jnp twin of the Pallas kernel)
# ---------------------------------------------------------------------------


def _mask_block(
    q_pos: jax.Array,  # [cq]
    k_pos: jax.Array,  # [ck]
    *,
    causal: bool,
    window: int,
) -> jax.Array:
    """[cq, ck] boolean validity mask."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window and window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return ok


def flash_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, Kv, hd]
    v: jax.Array,  # [B, T, Kv, hd]
    *,
    causal: bool = True,
    window: int = 0,
    logit_softcap: float = 0.0,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise online-softmax attention; never builds the [S, T] matrix.

    Ragged lengths are zero-padded to the chunk grid; padded *keys* are
    masked out (causally for causal attention, by valid length otherwise)
    and padded query rows are sliced away.
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    assert H % Kv == 0, (H, Kv)
    G = H // Kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    cq = min(chunk_q, S)
    ck = min(chunk_kv, T)
    S_real, T_real = S, T
    if S % cq or T % ck:
        S_pad = (S + cq - 1) // cq * cq
        T_pad = (T + ck - 1) // ck * ck
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        S, T = S_pad, T_pad
    nq, nk = S // cq, T // ck

    qb = q.reshape(B, nq, cq, Kv, G, hd)
    kb = k.reshape(B, nk, ck, Kv, hd)
    vb = v.reshape(B, nk, ck, Kv, hd)
    q_pos = q_offset + jnp.arange(S, dtype=jnp.int32).reshape(nq, cq)
    k_pos = jnp.arange(T, dtype=jnp.int32).reshape(nk, ck)

    def one_q_block(q_chunk, q_positions):
        # q_chunk: [B, cq, Kv, G, hd]; q_positions: [cq]
        from repro.distributed.vma import vary

        m0, l0, acc0 = vary((
            jnp.full((B, Kv, G, cq), _BIG_NEG, dtype=jnp.float32),
            jnp.zeros((B, Kv, G, cq), dtype=jnp.float32),
            jnp.zeros((B, Kv, G, cq, hd), dtype=jnp.float32),
        ))

        def kv_step(carry, inp):
            m, l, acc = carry
            k_chunk, v_chunk, k_positions = inp  # [B, ck, Kv, hd], [ck]
            s = jnp.einsum(
                "bqkgd,btkd->bkgqt", q_chunk, k_chunk, preferred_element_type=jnp.float32
            )
            s = s * scale
            if logit_softcap and logit_softcap > 0.0:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            ok = _mask_block(q_positions, k_positions, causal=causal, window=window)
            ok &= (k_positions < T_real)[None, :]  # padded keys never attended
            s = jnp.where(ok[None, None, None], s, _BIG_NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, v_chunk, preferred_element_type=jnp.float32
            )
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, acc0), (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), k_pos)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Kv, G, cq, hd]
        return jnp.einsum("bkgqd->bqkgd", out)

    out_blocks = jax.lax.map(
        lambda args: one_q_block(*args), (jnp.moveaxis(qb, 1, 0), q_pos)
    )  # [nq, B, cq, Kv, G, hd]
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, S, H, hd)
    return out[:, :S_real].astype(q.dtype)


# ---------------------------------------------------------------------------
# full layers (projections + rope + attention), train/prefill and decode
# ---------------------------------------------------------------------------


def _project_qkv(p: Params, x: jax.Array, positions, cfg, *, dtype, rope: bool):
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x, dtype=dtype)  # [B, S, H, hd]
    k = dense(p["wk"], x, dtype=dtype)
    v = dense(p["wv"], x, dtype=dtype)
    if cfg.qk_norm:
        q = norm(p["q_norm"], q, kind="rmsnorm")
        k = norm(p["k_norm"], k, kind="rmsnorm")
    if rope and cfg.use_rope:
        freqs = rope_freqs(hd, cfg.rope_fraction, cfg.rope_theta)
        q = apply_rope(q, positions, freqs)
        k = apply_rope(k, positions, freqs)
    return q, k, v


def attention_layer(
    p: Params,
    x: jax.Array,            # [B, S, D]
    positions: jax.Array,    # [B, S]
    cfg,
    *,
    kind: str,               # 'attn' | 'attn_local'
    dtype,
    causal: bool = True,
    memory: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn (k, v)
    return_kv: bool = False,
):
    """Train/prefill attention. Returns (out, (k, v) or None)."""
    if memory is None:
        q, k, v = _project_qkv(p, x, positions, cfg, dtype=dtype, rope=True)
    else:
        q = dense(p["wq"], x, dtype=dtype)
        if cfg.qk_norm:
            q = norm(p["q_norm"], q, kind="rmsnorm")
        k, v = memory
        causal = False
    window = cfg.attn_window if kind == "attn_local" else 0
    if getattr(cfg, "use_pallas", False):
        from repro.kernels.flash_attention import flash_attention_pallas

        out = flash_attention_pallas(
            q, k, v, causal=causal, window=window, logit_softcap=cfg.attn_softcap,
            block_q=cfg.attn_chunk_q, block_kv=cfg.attn_chunk_kv,
        )
    else:
        out = flash_attention(
            q,
            k,
            v,
            causal=causal,
            window=window,
            logit_softcap=cfg.attn_softcap,
            chunk_q=cfg.attn_chunk_q,
            chunk_kv=cfg.attn_chunk_kv,
        )
    out = jnp.einsum("bshd,hdm->bsm", out, p["wo"]["w"].astype(dtype))
    return (out, (k, v) if return_kv else None)


def init_kv_cache(cfg, batch: int, max_len: int, *, n_layers_of_kind: int, dtype) -> Dict:
    hd = cfg.resolved_head_dim
    shape = (n_layers_of_kind, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}


def decode_attention_layer(
    p: Params,
    x: jax.Array,          # [B, 1, D]
    cache_k: jax.Array,    # [B, T, Kv, hd]
    cache_v: jax.Array,
    pos: jax.Array,        # scalar int32 — cache *slot* to write (== abs pos unless rolling)
    cfg,
    *,
    kind: str,
    dtype,
    rolling: bool = False,     # T == attn_window ring buffer (local layers)
    abs_pos: Optional[jax.Array] = None,  # absolute token position (RoPE/mask)
):
    """One-token decode; returns (out [B,1,D], new_cache_k, new_cache_v).

    ``pos``/``abs_pos`` may be scalars or [B] vectors — continuous batching
    serves slots at different sequence positions in one decode batch.

    ``rolling=True`` treats the cache as a ring buffer of size T == window:
    the slot index is ``pos = abs_pos % T`` and, once ``abs_pos >= T-1``,
    every slot holds a key inside the window (slot occupancy mask
    ``t <= abs_pos`` covers both the warm-up and steady-state phases because
    slot indices never exceed T-1).  RoPE always uses the absolute position,
    so ring placement does not perturb the attention geometry.
    """
    B, _, _ = x.shape
    T = cache_k.shape[1]
    if abs_pos is None:
        abs_pos = pos
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    abs_b = jnp.broadcast_to(jnp.asarray(abs_pos, jnp.int32), (B,))
    positions = abs_b[:, None]
    q, k_new, v_new = _project_qkv(p, x, positions, cfg, dtype=dtype, rope=True)

    def write_row(c, new, p):
        return jax.lax.dynamic_update_slice_in_dim(c, new.astype(c.dtype), p, axis=0)

    cache_k = jax.vmap(write_row)(cache_k, k_new, pos_b)
    cache_v = jax.vmap(write_row)(cache_v, v_new, pos_b)

    Kv = cfg.n_kv_heads
    G = cfg.n_heads // Kv
    hd = cfg.resolved_head_dim
    qh = q.reshape(B, Kv, G, hd)
    s = jnp.einsum(
        "bkgd,btkd->bkgt", qh, cache_k.astype(dtype), preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    if cfg.attn_softcap:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    t_idx = jnp.arange(T, dtype=jnp.int32)
    # slot-occupancy mask; for rolling caches the window constraint is
    # implicit in the ring size, for linear caches it is applied explicitly
    ok = t_idx[None, None, None, :] <= abs_b[:, None, None, None]
    if not rolling and kind == "attn_local" and cfg.attn_window:
        ok &= t_idx[None, None, None, :] > (abs_b[:, None, None, None] - cfg.attn_window)
    s = jnp.where(ok, s, _BIG_NEG)
    # reductions over T lower to collectives when the cache is seq-sharded (CP)
    m = s.max(axis=-1, keepdims=True)
    pexp = jnp.exp(s - m)
    l = pexp.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgt,btkd->bkgd", (pexp / jnp.maximum(l, 1e-30)), cache_v.astype(dtype))
    out = out.reshape(B, 1, cfg.n_heads, hd)
    out = jnp.einsum("bshd,hdm->bsm", out.astype(dtype), p["wo"]["w"].astype(dtype))
    return out, cache_k, cache_v
