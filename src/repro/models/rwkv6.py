"""RWKV-6 "Finch" block: time-mix with data-dependent decay + channel-mix.

The WKV recurrence per head (head_dim C, state S ∈ ℝ^{C×C}):

    out_t = r_t · (S_{t-1} + (u ∘ k_t) ⊗ v_t)
    S_t   = diag(w_t) S_{t-1} + k_t ⊗ v_t

with w_t = exp(-exp(w₀ + LoRA(x_t))) the *data-dependent* per-channel decay
(the defining Finch feature).  Train/prefill uses a chunked formulation:
relative decays exp(L_t − L_τ) are exponentials of non-positive numbers, so
the chunk math is stable at any length; chunk size bounds the [T,T,C] score
tensor.  The Pallas kernel (:mod:`repro.kernels.rwkv6_scan`) mirrors this
chunking with the state carried in VMEM.

Simplification vs the released model: token-shift
lerps use learned per-channel μ rather than the data-dependent ddlerp LoRA;
decay keeps its LoRA.  This preserves the paper's architectural signature
(data-dependent decay, outer-product state) at the assigned dimensions.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, dense, init_dense

__all__ = [
    "init_rwkv_tmix",
    "rwkv_tmix",
    "init_rwkv_cmix",
    "rwkv_cmix",
    "wkv_chunked",
    "init_rwkv_cache",
]

_LORA_RANK = 64


def _heads(cfg) -> Tuple[int, int]:
    C = cfg.rwkv.head_dim
    assert cfg.d_model % C == 0
    return cfg.d_model // C, C


def init_rwkv_tmix(key, cfg, *, param_dtype) -> Params:
    D = cfg.d_model
    H, C = _heads(cfg)
    keys = jax.random.split(key, 10)
    r = min(_LORA_RANK, D)
    return {
        "mu": (0.5 * jnp.ones((5, D), dtype=jnp.float32)).astype(param_dtype),  # r,k,v,g,w
        "w_r": init_dense(keys[0], D, (D,), param_dtype=param_dtype),
        "w_k": init_dense(keys[1], D, (D,), param_dtype=param_dtype),
        "w_v": init_dense(keys[2], D, (D,), param_dtype=param_dtype),
        "w_g": init_dense(keys[3], D, (D,), param_dtype=param_dtype),
        "w_o": init_dense(keys[4], D, (D,), param_dtype=param_dtype),
        "decay_base": (-6.0 + 5.0 * jnp.linspace(0, 1, D) ** 0.7).astype(param_dtype),
        "decay_lora_a": init_dense(keys[5], D, (r,), param_dtype=param_dtype),
        "decay_lora_b": init_dense(keys[6], r, (D,), param_dtype=param_dtype, scale=0.01),
        "bonus": (jax.random.normal(keys[7], (H, C)) * 0.1).astype(param_dtype),
        "gn_scale": jnp.ones((D,), dtype=param_dtype),
        "gn_bias": jnp.zeros((D,), dtype=param_dtype),
    }


def _token_shift(x: jax.Array, last: Optional[jax.Array]) -> jax.Array:
    """x[t] ← x[t-1]; position 0 primed by ``last`` (decode carry) or zeros."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def wkv_chunked(
    r: jax.Array,  # [B, S, H, C]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # [B, S, H, C]  decay in (0, 1)
    u: jax.Array,  # [H, C] bonus
    *,
    chunk: int,
    s0: Optional[jax.Array] = None,  # [B, H, C, C]
) -> Tuple[jax.Array, jax.Array]:
    """Chunked WKV recurrence; returns (out [B,S,H,C], final state).

    Ragged tails are padded with w=1 (log-decay 0) and k=0, which leaves the
    carried state untouched; padded outputs are sliced away.
    """
    B, S, H, C = r.shape
    chunk = min(chunk, S)
    S_real = S
    if S % chunk:
        pad = (S + chunk - 1) // chunk * chunk - S
        zero = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, zero)
        k = jnp.pad(k, zero)
        v = jnp.pad(v, zero)
        w = jnp.pad(w, zero, constant_values=1.0)
        S += pad
    n = S // chunk
    if s0 is None:
        from repro.distributed.vma import vary

        s0 = vary(jnp.zeros((B, H, C, C), dtype=jnp.float32))

    logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))
    rc = r.reshape(B, n, chunk, H, C).astype(jnp.float32)
    kc = k.reshape(B, n, chunk, H, C).astype(jnp.float32)
    vc = v.reshape(B, n, chunk, H, C).astype(jnp.float32)
    lw = logw.reshape(B, n, chunk, H, C)

    tri_lt = jnp.tril(jnp.ones((chunk, chunk), dtype=bool), k=-1)  # τ < t

    def seg(s_prev, inp):
        rr, kk, vv, ll = inp  # [B,chunk,H,C] each
        L = jnp.cumsum(ll, axis=1)             # inclusive  L_t
        Lexc = L - ll                           # exclusive  L_{t-1}
        # inter-chunk: r_t ∘ exp(Lexc_t) against carried state
        r_dec = rr * jnp.exp(Lexc)
        out_inter = jnp.einsum("bthi,bhij->bthj", r_dec, s_prev)
        # intra-chunk: scores[t,τ] = Σ_i r_t[i] exp(Lexc_t[i] − L_τ[i]) k_τ[i]
        rel = Lexc[:, :, None] - L[:, None]     # [B,t,τ,H,C]
        rel = jnp.where(tri_lt[None, :, :, None, None], rel, -jnp.inf)
        att = jnp.einsum("bthi,btuhi,buhi->bthu", rr, jnp.exp(rel), kk)
        # diagonal (current token) bonus term
        diag = jnp.einsum("bthi,hi,bthi->bth", rr, u.astype(jnp.float32), kk)
        out = out_inter + jnp.einsum("bthu,buhj->bthj", att, vv) + diag[..., None] * vv
        # state update: S ← exp(L_T) ∘ S + Σ_τ exp(L_T − L_τ) k_τ ⊗ v_τ
        decay_all = jnp.exp(L[:, -1][:, None] - L)       # [B,τ,H,C]
        s_new = jnp.exp(L[:, -1])[..., None] * s_prev + jnp.einsum(
            "buhi,buhj->bhij", decay_all * kk, vv
        )
        return s_new, out

    s_fin, outs = jax.lax.scan(
        seg,
        s0,
        (
            jnp.moveaxis(rc, 1, 0),
            jnp.moveaxis(kc, 1, 0),
            jnp.moveaxis(vc, 1, 0),
            jnp.moveaxis(lw, 1, 0),
        ),
    )
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, C)
    return out[:, :S_real], s_fin


def _group_norm(x: jax.Array, scale, bias, H: int, C: int) -> jax.Array:
    """Per-head layernorm over C (RWKV's GroupNorm(H))."""
    B, S, D = x.shape
    xh = x.reshape(B, S, H, C).astype(jnp.float32)
    mean = xh.mean(axis=-1, keepdims=True)
    var = xh.var(axis=-1, keepdims=True)
    xh = (xh - mean) * jax.lax.rsqrt(var + 1e-5)
    out = xh.reshape(B, S, D) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def rwkv_tmix(
    p: Params,
    x: jax.Array,  # [B, S, D]
    cfg,
    *,
    dtype,
    state: Optional[Dict] = None,  # {'wkv': [B,H,C,C], 'shift': [B,1,D]}
):
    """Returns (out, new_state) — new_state is None unless ``state`` given."""
    H, C = _heads(cfg)
    shift_in = None if state is None else state["shift"]
    xs = _token_shift(x, shift_in)
    mu = p["mu"].astype(dtype)
    mixed = [x + (xs - x) * mu[i][None, None, :] for i in range(5)]
    mr, mk, mv, mg, mw = mixed

    r = dense(p["w_r"], mr, dtype=dtype).reshape(*x.shape[:2], H, C)
    k = dense(p["w_k"], mk, dtype=dtype).reshape(*x.shape[:2], H, C)
    v = dense(p["w_v"], mv, dtype=dtype).reshape(*x.shape[:2], H, C)
    g = dense(p["w_g"], mg, dtype=dtype)
    # data-dependent decay (Finch): w = exp(-exp(base + LoRA(mw)))
    lora = dense(p["decay_lora_b"], jnp.tanh(dense(p["decay_lora_a"], mw, dtype=dtype)), dtype=dtype)
    decay_log = p["decay_base"].astype(jnp.float32)[None, None, :] + lora.astype(jnp.float32)
    w = jnp.exp(-jnp.exp(decay_log)).reshape(*x.shape[:2], H, C)

    s0 = None if state is None else state["wkv"]
    if getattr(cfg, "use_pallas", False) and s0 is None:
        from repro.kernels.ops import wkv6 as _wkv_op

        out, s_fin = _wkv_op(r, k, v, w, p["bonus"], chunk=cfg.ssm_chunk, use_pallas=True)
    else:
        out, s_fin = wkv_chunked(r, k, v, w, p["bonus"], chunk=cfg.ssm_chunk, s0=s0)
    out = _group_norm(out.reshape(*x.shape[:2], H * C).astype(dtype), p["gn_scale"], p["gn_bias"], H, C)
    out = out * jax.nn.silu(g)
    out = dense(p["w_o"], out, dtype=dtype)
    new_state = None
    if state is not None:
        new_state = {"wkv": s_fin, "shift": x[:, -1:, :]}
    return out, new_state


def init_rwkv_cmix(key, cfg, *, param_dtype) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    keys = jax.random.split(key, 3)
    return {
        "mu": (0.5 * jnp.ones((2, D), dtype=jnp.float32)).astype(param_dtype),  # k, r
        "w_k": init_dense(keys[0], D, (F,), param_dtype=param_dtype),
        "w_v": init_dense(keys[1], F, (D,), param_dtype=param_dtype),
        "w_r": init_dense(keys[2], D, (D,), param_dtype=param_dtype),
    }


def rwkv_cmix(
    p: Params,
    x: jax.Array,
    cfg,
    *,
    dtype,
    state: Optional[Dict] = None,  # {'shift': [B,1,D]}
):
    shift_in = None if state is None else state["shift"]
    xs = _token_shift(x, shift_in)
    mu = p["mu"].astype(dtype)
    mk = x + (xs - x) * mu[0][None, None, :]
    mr = x + (xs - x) * mu[1][None, None, :]
    k = jnp.square(jax.nn.relu(dense(p["w_k"], mk, dtype=dtype)))
    kv = dense(p["w_v"], k, dtype=dtype)
    out = jax.nn.sigmoid(dense(p["w_r"], mr, dtype=dtype)) * kv
    new_state = None if state is None else {"shift": x[:, -1:, :]}
    return out, new_state


def init_rwkv_cache(cfg, batch: int, *, n_layers_of_kind: int, dtype) -> Dict:
    H, C = _heads(cfg)
    n = n_layers_of_kind
    return {
        "wkv": jnp.zeros((n, batch, H, C, C), dtype=jnp.float32),
        "tshift": jnp.zeros((n, batch, 1, cfg.d_model), dtype=dtype),
        "cshift": jnp.zeros((n, batch, 1, cfg.d_model), dtype=dtype),
    }
