"""Attention: GQA projections + chunked (flash-style) attention in pure JAX.

The forward pass never materializes the [S, T] score matrix: it is doubly
blocked (outer loop over query chunks, ``lax.scan`` over KV chunks) with the
standard running-max/running-sum online softmax.  This is the mathematical
twin of the Pallas TPU kernel in :mod:`repro.kernels.flash_attention`; the
model dispatches to the kernel when ``cfg.use_pallas`` is set and to this
implementation otherwise (CPU dry-runs, correctness oracles).

Multi-head latent attention (MLA, DeepSeek-V2/V3) shares this attention:
its keys and values are expanded from one normed latent per token, and its
decode cache holds only that latent and the rotary key.

Supports: causal and bidirectional attention, sliding-window masks (Gemma2
local layers), attention-logit softcapping, GQA with arbitrary group counts,
partial-fraction RoPE, and single-token decode against a KV cache (the decode
formulation is context-parallel friendly: reductions over the KV axis lower
to collectives when the cache is sequence-sharded).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, apply_rope, dense, init_dense, init_norm, norm, rope_freqs, softcap

__all__ = [
    "init_attention",
    "flash_attention",
    "attention_layer",
    "decode_attention_layer",
    "init_kv_cache",
    "init_mla",
    "mla_layer",
    "decode_mla_layer",
    "init_mla_cache",
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
    v: jax.Array,  # [B, T, Kv, dv]
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
    and padded query rows are sliced away.  Key blocks that the causal or
    window mask hides from a whole query block are skipped.  The backward
    pass recomputes each block's scores from the saved row log-sum-exp, so
    what it keeps is O(B·S·H·d), never one residual per block pair.  The
    value head size (``dv``) may differ from the query/key one.
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    assert H % Kv == 0, (H, Kv)
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
    grid = _Grid(causal, int(window or 0), float(logit_softcap or 0.0), cq, ck,
                 int(q_offset), float(scale), T_real)
    out = _flash(q, k, v, grid)
    return out[:, :S_real].astype(q.dtype)


class _Grid(NamedTuple):
    """The static geometry of one flash_attention call."""

    causal: bool
    window: int
    softcap: float
    cq: int
    ck: int
    q_offset: int
    scale: float
    t_real: int

    def kv_range(self, i, nk: int):
        """Key blocks [lo, hi) that query block ``i`` (traced) can see."""
        q_first = self.q_offset + i * self.cq
        hi = nk
        if self.causal:
            hi = jnp.minimum(nk, (q_first + self.cq - 1) // self.ck + 1)
        lo = 0
        if self.window > 0:
            lo = jnp.maximum(0, (q_first - self.window + 1) // self.ck)
        return lo, hi

    def q_range(self, j, nq: int):
        """Query blocks [lo, hi) that can see key block ``j`` (traced)."""
        lo = 0
        if self.causal:
            lo = jnp.clip((j * self.ck - self.q_offset) // self.cq, 0, nq)
        hi = nq
        if self.window > 0:
            last = ((j + 1) * self.ck + self.window - 2 - self.q_offset) // self.cq
            hi = jnp.clip(last + 1, 0, nq)
        return lo, hi

    def scores(self, q_chunk, k_chunk, q_positions, k_positions):
        """Scaled, capped and masked scores of one block pair [B, Kv, G, cq, ck],
        with the cap's tanh (or None) for the backward."""
        s = jnp.einsum("bqkgd,btkd->bkgqt", q_chunk, k_chunk, preferred_element_type=jnp.float32)
        s = s * self.scale
        capped = None
        if self.softcap > 0.0:
            capped = jnp.tanh(s / self.softcap)
            s = self.softcap * capped
        ok = _mask_block(q_positions, k_positions, causal=self.causal, window=self.window)
        ok &= (k_positions < self.t_real)[None, :]  # padded keys never attended
        return jnp.where(ok[None, None, None], s, _BIG_NEG), ok, capped


def _blocks(q, k, v, grid: _Grid):
    B, S, H, hd = q.shape
    T, Kv, dv = k.shape[1], k.shape[2], v.shape[-1]
    nq, nk = S // grid.cq, T // grid.ck
    qb = jnp.moveaxis(q.reshape(B, nq, grid.cq, Kv, H // Kv, hd), 1, 0)
    kb = jnp.moveaxis(k.reshape(B, nk, grid.ck, Kv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, grid.ck, Kv, dv), 1, 0)
    q_pos = grid.q_offset + jnp.arange(S, dtype=jnp.int32).reshape(nq, grid.cq)
    k_pos = jnp.arange(T, dtype=jnp.int32).reshape(nk, grid.ck)
    return qb, kb, vb, q_pos, k_pos


def _flash_fwd_blocks(q, k, v, grid: _Grid):
    """Output [B, S, H, dv] and row log-sum-exp [nq, B, Kv, G, cq]."""
    from repro.distributed.vma import vary

    B, S, H, _ = q.shape
    Kv, dv = k.shape[2], v.shape[-1]
    G = H // Kv
    qb, kb, vb, q_pos, k_pos = _blocks(q, k, v, grid)
    nq, nk = qb.shape[0], kb.shape[0]
    cq = grid.cq

    def one_q_block(i, q_chunk, q_positions):
        # q_chunk: [B, cq, Kv, G, hd]; q_positions: [cq]
        m0, l0, acc0 = vary((
            jnp.full((B, Kv, G, cq), _BIG_NEG, dtype=jnp.float32),
            jnp.zeros((B, Kv, G, cq), dtype=jnp.float32),
            jnp.zeros((B, Kv, G, cq, dv), dtype=jnp.float32),
        ))

        def kv_step(j, carry):
            m, l, acc = carry
            s, _, _ = grid.scores(q_chunk, kb[j], q_positions, k_pos[j])
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, vb[j], preferred_element_type=jnp.float32
            )
            return m_new, l_new, acc_new

        lo, hi = grid.kv_range(i, nk)
        m, l, acc = jax.lax.fori_loop(lo, hi, kv_step, (m0, l0, acc0))
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]  # [B, Kv, G, cq, dv]
        return jnp.einsum("bkgqd->bqkgd", out), m + jnp.log(l)

    out_blocks, lse = jax.lax.map(
        lambda args: one_q_block(*args), (jnp.arange(nq, dtype=jnp.int32), qb, q_pos)
    )  # [nq, B, cq, Kv, G, dv], [nq, B, Kv, G, cq]
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, S, H, dv)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, grid: _Grid):
    return _flash_fwd_blocks(q, k, v, grid)[0]


def _flash_fwd(q, k, v, grid: _Grid):
    out, lse = _flash_fwd_blocks(q, k, v, grid)
    return out, (q, k, v, out.astype(q.dtype), lse)


def _flash_bwd(grid: _Grid, res, g):
    """FlashAttention-2's backward: for each key block, the query blocks that
    see it; scores and probabilities are recomputed from the saved log-sum-exp."""
    q, k, v, out, lse = res
    B, S, H, hd = q.shape
    Kv, dv = k.shape[2], v.shape[-1]
    G = H // Kv
    qb, kb, vb, q_pos, k_pos = _blocks(q, k, v, grid)
    nq, nk = qb.shape[0], kb.shape[0]
    gb = jnp.moveaxis(g.reshape(B, nq, grid.cq, Kv, G, dv), 1, 0)
    ob = jnp.moveaxis(out.reshape(B, nq, grid.cq, Kv, G, dv), 1, 0)
    # D = rowsum(dO ∘ O), the softmax's Jacobian term: [nq, B, Kv, G, cq]
    delta = jnp.einsum("nbqkgd,nbqkgd->nbkgq", gb.astype(jnp.float32), ob.astype(jnp.float32))

    from repro.distributed.vma import vary

    def one_kv_block(dq, inp):
        j, k_chunk, v_chunk, k_positions = inp

        def q_step(i, carry):
            dk, dv_, dq = carry
            q_chunk, g_chunk = qb[i], gb[i]
            s, ok, capped = grid.scores(q_chunk, k_chunk, q_pos[i], k_positions)
            p = jnp.exp(s - lse[i][..., None])
            p = jnp.where(ok[None, None, None], p, 0.0)
            dv_ = dv_ + jnp.einsum("bkgqt,bqkgd->btkd", p, g_chunk, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgd,btkd->bkgqt", g_chunk, v_chunk, preferred_element_type=jnp.float32)
            ds = p * (dp - delta[i][..., None])
            if capped is not None:
                ds = ds * (1.0 - jnp.square(capped))
            ds = ds * grid.scale
            dk = dk + jnp.einsum("bkgqt,bqkgd->btkd", ds, q_chunk, preferred_element_type=jnp.float32)
            dq_i = jnp.einsum("bkgqt,btkd->bqkgd", ds, k_chunk, preferred_element_type=jnp.float32)
            dq = jax.lax.dynamic_update_index_in_dim(dq, dq[i] + dq_i, i, axis=0)
            return dk, dv_, dq

        zeros_k, zeros_v = vary((jnp.zeros(k_chunk.shape, jnp.float32),
                                 jnp.zeros(v_chunk.shape, jnp.float32)))
        lo, hi = grid.q_range(j, nq)
        dk, dv_, dq = jax.lax.fori_loop(lo, hi, q_step, (zeros_k, zeros_v, dq))
        return dq, (dk, dv_)

    dq0 = vary(jnp.zeros(qb.shape, jnp.float32))
    dq, (dk, dv_) = jax.lax.scan(
        one_kv_block, dq0, (jnp.arange(nk, dtype=jnp.int32), kb, vb, k_pos)
    )
    dq = jnp.moveaxis(dq, 0, 1).reshape(q.shape).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).reshape(k.shape).astype(k.dtype)
    dv_ = jnp.moveaxis(dv_, 0, 1).reshape(v.shape).astype(v.dtype)
    return dq, dk, dv_


_flash.defvjp(_flash_fwd, _flash_bwd)


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


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V3 MLA, no query compression)
# ---------------------------------------------------------------------------


def init_mla(key, cfg, *, param_dtype) -> Params:
    """wq [D, H, nope+rope]; wkv_a [D, latent+rope]; kv_norm [latent];
    wkv_b [latent, H, nope+v]; wo [H, v, D]."""
    m, H, D = cfg.mla, cfg.n_heads, cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_dense(k1, D, (H, m.qk_head_dim), param_dtype=param_dtype),
        "wkv_a": init_dense(k2, D, (m.kv_lora_rank + m.qk_rope_head_dim,), param_dtype=param_dtype),
        "kv_norm": init_norm("rmsnorm", m.kv_lora_rank, param_dtype=param_dtype),
        "wkv_b": init_dense(k3, m.kv_lora_rank, (H, m.qk_nope_head_dim + m.v_head_dim),
                            param_dtype=param_dtype),
        "wo": {
            "w": (
                jax.random.normal(k4, (H, m.v_head_dim, D), dtype=jnp.float32)
                / math.sqrt(H * m.v_head_dim)
            ).astype(param_dtype)
        },
    }


def _mla_project(p: Params, x: jax.Array, positions, cfg, *, dtype):
    """Queries [B, S, H, nope+rope] (rotary part rotated), the normed latent
    [B, S, latent] and the rotated rotary key [B, S, rope]: what the cache keeps."""
    m = cfg.mla
    q = dense(p["wq"], x, dtype=dtype)
    kva = dense(p["wkv_a"], x, dtype=dtype)
    c = norm(p["kv_norm"], kva[..., : m.kv_lora_rank], kind="rmsnorm")
    # interleaved pairs: the source de-interleaves before rotate_half, which
    # gives the same scores
    freqs = rope_freqs(m.qk_rope_head_dim, 1.0, cfg.rope_theta)
    q_pe = apply_rope(q[..., m.qk_nope_head_dim :], positions, freqs)
    k_pe = apply_rope(kva[..., None, m.kv_lora_rank :], positions, freqs)[:, :, 0]
    q = jnp.concatenate([q[..., : m.qk_nope_head_dim], q_pe], axis=-1)
    return q, c, k_pe


def _mla_keys_values(p: Params, c: jax.Array, k_pe: jax.Array, cfg, *, dtype):
    """Per-head keys [B, T, H, nope+rope] and values [B, T, H, v] from the latent."""
    m = cfg.mla
    kv = dense(p["wkv_b"], c, dtype=dtype)
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim :]
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :].astype(k_nope.dtype), (*k_nope.shape[:-1], k_pe.shape[-1]))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def mla_layer(p: Params, x: jax.Array, positions: jax.Array, cfg, *, dtype, return_cache: bool = False):
    """Train/prefill MLA, causal. Returns (out, (latent, rotary key) or None)."""
    q, c, k_pe = _mla_project(p, x, positions, cfg, dtype=dtype)
    k, v = _mla_keys_values(p, c, k_pe, cfg, dtype=dtype)
    out = flash_attention(
        q, k, v, causal=True, scale=1.0 / math.sqrt(cfg.mla.qk_head_dim),
        chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
    )
    out = jnp.einsum("bshd,hdm->bsm", out, p["wo"]["w"].astype(dtype))
    return out, ((c, k_pe) if return_cache else None)


def init_mla_cache(cfg, batch: int, max_len: int, *, n_layers_of_kind: int, dtype) -> Dict:
    """The latent cache: kv_lora_rank + qk_rope_head_dim numbers a token and layer."""
    m = cfg.mla
    return {
        "c": jnp.zeros((n_layers_of_kind, batch, max_len, m.kv_lora_rank), dtype=dtype),
        "k_pe": jnp.zeros((n_layers_of_kind, batch, max_len, m.qk_rope_head_dim), dtype=dtype),
    }


def decode_mla_layer(p: Params, x: jax.Array, cache_c: jax.Array, cache_kpe: jax.Array, pos, cfg, *, dtype):
    """One-token MLA decode against the latent cache; ``pos`` a scalar or [B].

    Keys and values are expanded from the cached latent at every step (the
    plain form, not the absorbed one).  Returns (out [B,1,D], cache_c, cache_kpe)."""
    B = x.shape[0]
    T = cache_c.shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, c_new, kpe_new = _mla_project(p, x, pos_b[:, None], cfg, dtype=dtype)

    def write_row(cache, new, at):
        return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype), at, axis=0)

    cache_c = jax.vmap(write_row)(cache_c, c_new, pos_b)
    cache_kpe = jax.vmap(write_row)(cache_kpe, kpe_new, pos_b)
    k, v = _mla_keys_values(p, cache_c.astype(dtype), cache_kpe.astype(dtype), cfg, dtype=dtype)
    s = jnp.einsum("bhd,bthd->bht", q[:, 0], k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(cfg.mla.qk_head_dim)
    ok = jnp.arange(T, dtype=jnp.int32)[None, None, :] <= pos_b[:, None, None]
    s = jnp.where(ok, s, _BIG_NEG)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", prob.astype(dtype), v)[:, None]
    out = jnp.einsum("bshd,hdm->bsm", out.astype(dtype), p["wo"]["w"].astype(dtype))
    return out, cache_c, cache_kpe
