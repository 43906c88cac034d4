"""Encoder-decoder LM backbone (Seamless-M4T-medium's text/speech core).

Per the assignment spec the modality frontend is a **stub**: the model
consumes precomputed frame embeddings (``batch["frames"]`` of shape
[B, S_enc, frontend_dim]) as the encoder input; the decoder is a standard
causal LM with cross-attention into the encoder output.

Layer stacks follow the same stacked-parameter + ``lax.scan`` compilation
strategy as :mod:`repro.models.transformer` — one scan over encoder layers,
one over decoder layers, so the HLO stays one-layer-sized at any depth.

Decode caches: per-decoder-layer self-attention K/V (written per step) and
cross-attention K/V (projected once from the encoder output at prefill,
read-only afterwards).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .attention import (
    attention_layer,
    decode_attention_layer,
    flash_attention,
    init_attention,
)
from .layers import (
    Params,
    dense,
    embed,
    init_dense,
    init_embedding,
    init_mlp,
    init_norm,
    mlp,
    norm,
    softcap,
    unembed,
)

__all__ = [
    "init_encdec",
    "encode",
    "train_loss",
    "prefill",
    "decode_step",
    "init_decode_cache",
    "enc_len_for",
]


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def enc_len_for(cfg, seq_len: int) -> int:
    """Encoder (frame) length for a given decoder length.

    The audio frontend downsamples aggressively; we model the backbone's
    encoder length as seq_len // 4 (an assumption),
    clamped to at least one attention chunk.
    """
    return max(seq_len // 4, min(seq_len, 16))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_enc_layer(key, cfg, pdt) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
        "attn": init_attention(k1, cfg, param_dtype=pdt),
        "norm2": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
        "ffn": init_mlp(k2, cfg.d_model, cfg.d_ff, activation=cfg.activation, param_dtype=pdt),
    }


def _init_dec_layer(key, cfg, pdt) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
        "self_attn": init_attention(k1, cfg, param_dtype=pdt),
        "norm_x": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
        "cross_attn": init_attention(k2, cfg, param_dtype=pdt),
        "norm2": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
        "ffn": init_mlp(k3, cfg.d_model, cfg.d_ff, activation=cfg.activation, param_dtype=pdt),
    }


def init_encdec(key, cfg) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    k_embed, k_enc, k_dec, k_front = jax.random.split(key, 4)
    params: Params = {
        "embed": init_embedding(k_embed, cfg.vocab_size, cfg.d_model, param_dtype=pdt),
        "frontend_proj": init_dense(k_front, cfg.frontend_dim or cfg.d_model, (cfg.d_model,), param_dtype=pdt),
        "enc_final_norm": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
        "final_norm": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt),
    }
    enc_keys = jax.random.split(k_enc, cfg.n_enc_layers)
    params["enc_layers"] = jax.vmap(lambda k: _init_enc_layer(k, cfg, pdt))(enc_keys)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    params["dec_layers"] = jax.vmap(lambda k: _init_dec_layer(k, cfg, pdt))(dec_keys)
    return params


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def encode(params, frames: jax.Array, cfg) -> jax.Array:
    """frames: [B, S_enc, frontend_dim] → encoder states [B, S_enc, D]."""
    dt = _dtype(cfg)
    x = dense(params["frontend_proj"], frames.astype(dt), dtype=dt)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, lp):
        h = norm(lp["norm1"], x, kind=cfg.norm)
        a, _ = attention_layer(lp["attn"], h, positions, cfg, kind="attn", dtype=dt, causal=False)
        x = x + a
        h = norm(lp["norm2"], x, kind=cfg.norm)
        x = x + mlp(lp["ffn"], h, activation=cfg.activation, dtype=dt)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return norm(params["enc_final_norm"], x, kind=cfg.norm)


# ---------------------------------------------------------------------------
# decoder (train / prefill / decode)
# ---------------------------------------------------------------------------


def _cross_kv(lp, enc_out, cfg, dt):
    k = dense(lp["cross_attn"]["wk"], enc_out, dtype=dt)
    v = dense(lp["cross_attn"]["wv"], enc_out, dtype=dt)
    if cfg.qk_norm:
        k = norm(lp["cross_attn"]["k_norm"], k, kind="rmsnorm")
    return k, v


def _dec_body(cfg, dt, enc_out):
    def body(x, lp, positions):
        h = norm(lp["norm1"], x, kind=cfg.norm)
        a, _ = attention_layer(lp["self_attn"], h, positions, cfg, kind="attn", dtype=dt)
        x = x + a
        h = norm(lp["norm_x"], x, kind=cfg.norm)
        ck, cv = _cross_kv(lp, enc_out, cfg, dt)
        a, _ = attention_layer(lp["cross_attn"], h, positions, cfg, kind="attn", dtype=dt, memory=(ck, cv))
        x = x + a
        h = norm(lp["norm2"], x, kind=cfg.norm)
        x = x + mlp(lp["ffn"], h, activation=cfg.activation, dtype=dt)
        return x

    return body


def _hidden(params, batch: Dict[str, jax.Array], cfg) -> jax.Array:
    dt = _dtype(cfg)
    enc_out = encode(params, batch["frames"], cfg)
    x = embed(params["embed"], batch["tokens"], dtype=dt)
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    body = _dec_body(cfg, dt, enc_out)

    def scan_fn(x, lp):
        return body(x, lp, positions), None

    if cfg.remat != "none":
        scan_fn = jax.checkpoint(scan_fn, prevent_cse=False)
    x, _ = jax.lax.scan(scan_fn, x, params["dec_layers"])
    return norm(params["final_norm"], x, kind=cfg.norm)


def train_loss(params, batch, cfg, *, loss_chunk: int = 256):
    """Seq-chunked CE over the decoder; encoder runs once."""
    x = _hidden(params, batch, cfg)
    targets = batch["targets"]
    B, S = targets.shape
    c = min(loss_chunk, S)
    assert S % c == 0
    n = S // c
    xc = x.reshape(B, n, c, -1)
    tc = targets.reshape(B, n, c)

    def chunk_loss(carry, inp):
        xx, tt = inp
        logits = unembed(params["embed"], xx, dtype=_dtype(cfg)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # vocab-sharding-safe CE (see transformer.train_loss)
        onehot = jax.nn.one_hot(tt, logits.shape[-1], dtype=logits.dtype)
        picked = jnp.sum(logits * onehot, axis=-1)
        return carry + (lse - picked).sum(), None

    if getattr(cfg, "remat_loss_chunk", False):
        chunk_loss = jax.checkpoint(chunk_loss, prevent_cse=False)

    from repro.distributed.vma import vary

    total, _ = jax.lax.scan(
        chunk_loss, vary(jnp.zeros((), jnp.float32)), (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(tc, 1, 0))
    )
    loss = total / (B * S)
    return loss, {"loss": loss}


def init_decode_cache(cfg, batch: int, max_len: int, enc_len: int) -> Dict:
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    L = cfg.n_layers
    return {
        "self_k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, hd), dtype=dt),
        "self_v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, hd), dtype=dt),
        "cross_k": jnp.zeros((L, batch, enc_len, cfg.n_kv_heads, hd), dtype=dt),
        "cross_v": jnp.zeros((L, batch, enc_len, cfg.n_kv_heads, hd), dtype=dt),
    }


def prefill(params, batch, cfg, *, max_len: int):
    """Encode + run the prompt through the decoder, building all caches."""
    dt = _dtype(cfg)
    enc_out = encode(params, batch["frames"], cfg)
    x = embed(params["embed"], batch["tokens"], dtype=dt)
    B, S = batch["tokens"].shape
    enc_len = enc_out.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cache = init_decode_cache(cfg, B, max_len, enc_len)

    def body(x, inp):
        lp, lc = inp
        nc: Dict[str, Any] = {}
        h = norm(lp["norm1"], x, kind=cfg.norm)
        a, kv = attention_layer(lp["self_attn"], h, positions, cfg, kind="attn", dtype=dt, return_kv=True)
        k_new, v_new = kv
        nc["self_k"] = jax.lax.dynamic_update_slice_in_dim(lc["self_k"], k_new.astype(dt), 0, axis=1)
        nc["self_v"] = jax.lax.dynamic_update_slice_in_dim(lc["self_v"], v_new.astype(dt), 0, axis=1)
        x = x + a
        h = norm(lp["norm_x"], x, kind=cfg.norm)
        ck, cv = _cross_kv(lp, enc_out, cfg, dt)
        nc["cross_k"], nc["cross_v"] = ck.astype(dt), cv.astype(dt)
        a, _ = attention_layer(lp["cross_attn"], h, positions, cfg, kind="attn", dtype=dt, memory=(ck, cv))
        x = x + a
        h = norm(lp["norm2"], x, kind=cfg.norm)
        x = x + mlp(lp["ffn"], h, activation=cfg.activation, dtype=dt)
        return x, nc

    x, new_cache = jax.lax.scan(body, x, (params["dec_layers"], cache))
    x = norm(params["final_norm"], x, kind=cfg.norm)
    logits = unembed(params["embed"], x[:, -1:, :], dtype=dt)
    return new_cache, softcap(logits, cfg.final_softcap)


def decode_step(params, cache, token: jax.Array, pos: jax.Array, cfg):
    """One decoder step against self- and cross-attention caches."""
    dt = _dtype(cfg)
    x = embed(params["embed"], token, dtype=dt)
    B = token.shape[0]
    hd = cfg.resolved_head_dim
    Kv, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads

    def body(x, inp):
        lp, lc = inp
        nc = dict(lc)
        h = norm(lp["norm1"], x, kind=cfg.norm)
        a, ck_new, cv_new = decode_attention_layer(
            lp["self_attn"], h, lc["self_k"], lc["self_v"], pos, cfg, kind="attn", dtype=dt
        )
        nc["self_k"], nc["self_v"] = ck_new, cv_new
        x = x + a
        h = norm(lp["norm_x"], x, kind=cfg.norm)
        # cross-attention: single query against the fixed encoder K/V
        q = dense(lp["cross_attn"]["wq"], h, dtype=dt).reshape(B, Kv, G, hd)
        s = jnp.einsum("bkgd,btkd->bkgt", q, lc["cross_k"].astype(dt),
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bkgt,btkd->bkgd", p, lc["cross_v"].astype(dt)).reshape(B, 1, cfg.n_heads, hd)
        a = jnp.einsum("bshd,hdm->bsm", a.astype(dt), lp["cross_attn"]["wo"]["w"].astype(dt))
        x = x + a
        h = norm(lp["norm2"], x, kind=cfg.norm)
        x = x + mlp(lp["ffn"], h, activation=cfg.activation, dtype=dt)
        return x, nc

    x, new_cache = jax.lax.scan(body, x, (params["dec_layers"], cache))
    x = norm(params["final_norm"], x, kind=cfg.norm)
    logits = unembed(params["embed"], x, dtype=dt)
    return new_cache, softcap(logits, cfg.final_softcap)
