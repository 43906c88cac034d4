"""Mamba-1 selective SSM block (Jamba's sequence mixer).

TPU adaptation: the CUDA selective-scan kernel is a sequential
scan parallelized across channels.  Here the train/prefill path uses a
*chunked associative scan*: ``lax.scan`` over sequence chunks (bounding live
memory) with a numerically-stable ``lax.associative_scan`` inside each chunk
(the composition (a₂·a₁, a₂·b₁+b₂) never exponentiates positive sums).  The
Pallas kernel in :mod:`repro.kernels.mamba_scan` implements the same chunking
with the time loop in VMEM.

State layout: h ∈ [B, d_inner, d_state]; A is diagonal (d_inner × d_state),
input-dependent Δ, B, C as in the paper.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, dense, init_dense

__all__ = [
    "init_mamba",
    "mamba_layer",
    "mamba_layer_with_state",
    "mamba_decode_step",
    "init_mamba_cache",
    "ssm_chunked_scan",
]


def _d_inner(cfg) -> int:
    return cfg.mamba.expand * cfg.d_model


def _dt_rank(cfg) -> int:
    return cfg.mamba.dt_rank or max(1, math.ceil(cfg.d_model / 16))


def init_mamba(key, cfg, *, param_dtype) -> Params:
    m = cfg.mamba
    di, dr, ds = _d_inner(cfg), _dt_rank(cfg), m.d_state
    keys = jax.random.split(key, 7)
    # S4D-real initialization for A
    a = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))
    dt_bias = jnp.log(jnp.expm1(jnp.exp(
        jax.random.uniform(keys[5], (di,), minval=math.log(1e-3), maxval=math.log(1e-1))
    )))
    return {
        "in_proj": init_dense(keys[0], cfg.d_model, (2 * di,), param_dtype=param_dtype),
        "conv_w": (jax.random.normal(keys[1], (m.d_conv, di), dtype=jnp.float32) / math.sqrt(m.d_conv)).astype(param_dtype),
        "conv_b": jnp.zeros((di,), dtype=param_dtype),
        "x_proj": init_dense(keys[2], di, (dr + 2 * ds,), param_dtype=param_dtype),
        "dt_proj": init_dense(keys[3], dr, (di,), bias=True, param_dtype=param_dtype),
        "A_log": jnp.log(a).astype(param_dtype),
        "D": jnp.ones((di,), dtype=param_dtype),
        "out_proj": init_dense(keys[4], di, (cfg.d_model,), param_dtype=param_dtype),
        "dt_bias": dt_bias.astype(param_dtype),
    }


def _causal_depthwise_conv(x: jax.Array, w: jax.Array, b: jax.Array, *, init_state=None):
    """x: [B,S,di], w: [K,di] → causal depthwise conv, optional carry-in.

    Returns (y [B,S,di], tail [B,K-1,di]) where tail primes the next segment.
    """
    K = w.shape[0]
    if init_state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), dtype=x.dtype)
    else:
        pad = init_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # [B, S+K-1, di]
    y = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    y = y + b[None, None, :]
    tail = xp[:, -(K - 1):, :] if K > 1 else jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)
    return y, tail


def ssm_chunked_scan(
    u: jax.Array,      # [B, S, di]  (post-conv activations)
    delta: jax.Array,  # [B, S, di]  (softplus'd step sizes)
    A: jax.Array,      # [di, ds]    (negative; -exp(A_log))
    Bmat: jax.Array,   # [B, S, ds]
    Cmat: jax.Array,   # [B, S, ds]
    *,
    chunk: int,
    h0: jax.Array = None,  # [B, di, ds]
) -> Tuple[jax.Array, jax.Array]:
    """Selective scan  h_t = exp(Δ_t A)·h_{t-1} + Δ_t B_t u_t ;  y_t = C_t·h_t.

    Chunked: sequential over S/chunk segments, associative scan within.
    Returns (y [B,S,di], h_final [B,di,ds]).
    """
    Bsz, S, di = u.shape
    ds = A.shape[1]
    chunk = min(chunk, S)
    S_real = S
    if S % chunk:
        # ragged tail: Δ=0 padding ⇒ decay=1, drive=0 ⇒ state untouched
        pad = (S + chunk - 1) // chunk * chunk - S
        zero3 = ((0, 0), (0, pad), (0, 0))
        u = jnp.pad(u, zero3)
        delta = jnp.pad(delta, zero3)
        Bmat = jnp.pad(Bmat, zero3)
        Cmat = jnp.pad(Cmat, zero3)
        S += pad
    n = S // chunk

    decay = jnp.exp(delta[..., None] * A[None, None])          # [B,S,di,ds]
    drive = (delta * u)[..., None] * Bmat[:, :, None, :]       # [B,S,di,ds]

    decay_c = decay.reshape(Bsz, n, chunk, di, ds)
    drive_c = drive.reshape(Bsz, n, chunk, di, ds)
    C_c = Cmat.reshape(Bsz, n, chunk, ds)

    if h0 is None:
        from repro.distributed.vma import vary

        h0 = vary(jnp.zeros((Bsz, di, ds), dtype=jnp.float32))

    def seg(h_prev, inp):
        dec, drv, c = inp  # [B,chunk,di,ds] ×2, [B,chunk,ds]

        def compose(left, right):
            a1, b1 = left
            a2, b2 = right
            return a1 * a2, a2 * b1 + b2

        a_run, b_run = jax.lax.associative_scan(
            compose, (dec.astype(jnp.float32), drv.astype(jnp.float32)), axis=1
        )
        h_all = a_run * h_prev[:, None] + b_run                 # [B,chunk,di,ds]
        y = jnp.einsum("btdn,btn->btd", h_all, c.astype(jnp.float32))
        return h_all[:, -1], y

    h_final, ys = jax.lax.scan(
        seg,
        h0,
        (jnp.moveaxis(decay_c, 1, 0), jnp.moveaxis(drive_c, 1, 0), jnp.moveaxis(C_c, 1, 0)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, di)
    return y[:, :S_real], h_final


def _ssm_inputs(p: Params, x: jax.Array, cfg, *, dtype):
    """Shared projection pipeline; returns (u, z, delta, A, B, C, conv_tail_in)."""
    di, dr, ds = _d_inner(cfg), _dt_rank(cfg), cfg.mamba.d_state
    xz = dense(p["in_proj"], x, dtype=dtype)
    u, z = jnp.split(xz, 2, axis=-1)
    return u, z, di, dr, ds


def mamba_layer(p: Params, x: jax.Array, cfg, *, dtype) -> jax.Array:
    """Train/prefill forward, x: [B,S,D] → [B,S,D]."""
    u, z, di, dr, ds = _ssm_inputs(p, x, cfg, dtype=dtype)
    u, _ = _causal_depthwise_conv(u, p["conv_w"].astype(dtype), p["conv_b"].astype(dtype))
    u = jax.nn.silu(u)
    dbc = dense(p["x_proj"], u, dtype=dtype)
    dt, Bmat, Cmat = jnp.split(dbc, [dr, dr + ds], axis=-1)
    delta = jax.nn.softplus(
        dense(p["dt_proj"], dt, dtype=dtype).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32)
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if getattr(cfg, "use_pallas", False):
        from repro.kernels.ops import mamba_scan as _scan_op

        y, _ = _scan_op(
            u.astype(jnp.float32), delta, A,
            Bmat.astype(jnp.float32), Cmat.astype(jnp.float32),
            chunk=cfg.ssm_chunk, use_pallas=True,
        )
    else:
        y, _ = ssm_chunked_scan(
            u.astype(jnp.float32), delta, A, Bmat.astype(jnp.float32), Cmat.astype(jnp.float32),
            chunk=cfg.ssm_chunk,
        )
    y = y + u.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :]
    y = y.astype(dtype) * jax.nn.silu(z)
    return dense(p["out_proj"], y, dtype=dtype)


def mamba_layer_with_state(p: Params, x: jax.Array, cfg, *, dtype):
    """Prefill forward that also returns the decode carry.

    Returns (out [B,S,D], conv_tail [B,K-1,di], h_final [B,di,ds]).
    """
    u, z, di, dr, ds = _ssm_inputs(p, x, cfg, dtype=dtype)
    u, tail = _causal_depthwise_conv(u, p["conv_w"].astype(dtype), p["conv_b"].astype(dtype))
    u = jax.nn.silu(u)
    dbc = dense(p["x_proj"], u, dtype=dtype)
    dt, Bmat, Cmat = jnp.split(dbc, [dr, dr + ds], axis=-1)
    delta = jax.nn.softplus(
        dense(p["dt_proj"], dt, dtype=dtype).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32)
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h_final = ssm_chunked_scan(
        u.astype(jnp.float32), delta, A, Bmat.astype(jnp.float32), Cmat.astype(jnp.float32),
        chunk=cfg.ssm_chunk,
    )
    y = y + u.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :]
    y = y.astype(dtype) * jax.nn.silu(z)
    return dense(p["out_proj"], y, dtype=dtype), tail, h_final


def init_mamba_cache(cfg, batch: int, *, n_layers_of_kind: int, dtype) -> Dict:
    di, ds, K = _d_inner(cfg), cfg.mamba.d_state, cfg.mamba.d_conv
    return {
        "conv": jnp.zeros((n_layers_of_kind, batch, K - 1, di), dtype=dtype),
        "ssm": jnp.zeros((n_layers_of_kind, batch, di, ds), dtype=jnp.float32),
    }


def mamba_decode_step(
    p: Params,
    x: jax.Array,        # [B, 1, D]
    conv_state: jax.Array,  # [B, K-1, di]
    ssm_state: jax.Array,   # [B, di, ds]
    cfg,
    *,
    dtype,
):
    """One-token step; returns (out [B,1,D], conv_state, ssm_state)."""
    u, z, di, dr, ds = _ssm_inputs(p, x, cfg, dtype=dtype)
    u, tail = _causal_depthwise_conv(
        u, p["conv_w"].astype(dtype), p["conv_b"].astype(dtype), init_state=conv_state
    )
    u = jax.nn.silu(u)
    dbc = dense(p["x_proj"], u, dtype=dtype)
    dt, Bmat, Cmat = jnp.split(dbc, [dr, dr + ds], axis=-1)
    delta = jax.nn.softplus(
        dense(p["dt_proj"], dt, dtype=dtype).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32)
    )  # [B,1,di]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(delta[..., None] * A[None, None])[:, 0]        # [B,di,ds]
    drive = ((delta * u.astype(jnp.float32))[..., None] * Bmat.astype(jnp.float32)[:, :, None, :])[:, 0]
    h = decay * ssm_state + drive
    y = jnp.einsum("bdn,bn->bd", h, Cmat.astype(jnp.float32)[:, 0])[:, None, :]
    y = y + u.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :]
    y = y.astype(dtype) * jax.nn.silu(z)
    out = dense(p["out_proj"], y, dtype=dtype)
    return out, tail.astype(conv_state.dtype), h
