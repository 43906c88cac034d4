"""Chunked RWKV-6 WKV recurrence for TPU (``pl.pallas_call`` + BlockSpecs).

TPU adaptation of the CUDA wkv6 kernel: the GPU kernel runs
one thread per channel and serializes over time; a mechanical port would
leave the MXU idle.  Instead the sequence is processed in **chunks**: the
O(C×C) state crosses chunk boundaries (the only sequential dependence) while
all intra-chunk work is dense [chunk, C]×[C, C] / [chunk, chunk] matmul-like
contractions — the SCISPACE theme of "keep bulk work local, move only the
small state" applied at the register/VMEM level.

Grid ``(B, H, n_chunks)`` with the chunk index innermost; the running state
S ∈ ℝ^{C×C} (f32) persists in VMEM scratch across chunk steps.  Per chunk:

    L_t   = cumsum(log w)                      (inclusive), Lx = L - log w
    inter = (r ∘ exp(Lx)) @ S                  [chunk, C] — MXU
    att[t,u] = Σ_i r_t,i · exp(Lx_t,i − L_u,i) · k_u,i   (u < t, strictly)
    diag[t]  = Σ_i r_t,i · u_i · k_t,i         (current-token bonus)
    out   = inter + att @ v + diag ∘ v
    S     ← exp(L_last) ∘ S + Σ_u exp(L_last − L_u) k_u ⊗ v_u

All exponentials have non-positive arguments (log w ≤ 0 and u ≤ t), so the
chunk math is stable at any chunk size — the same invariant the pure-jnp
twin :func:`repro.models.rwkv6.wkv_chunked` relies on.  VMEM per step:
~5·chunk·C + chunk² + C² floats (chunk=128, C=64 → ~0.3 MB).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_pallas"]


def _wkv_kernel(
    r_ref,   # [1, 1, cs, C]
    k_ref,   # [1, 1, cs, C]
    v_ref,   # [1, 1, cs, C]
    lw_ref,  # [1, 1, cs, C]  log-decay (≤ 0)
    u_ref,   # [1, 1, C]      bonus for this head
    o_ref,   # [1, 1, cs, C]
    s_ref,   # VMEM [C, C] running state
    *,
    cs: int,
    C: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)          # [cs, C]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # [1, C]

    # inclusive prefix sum L_t over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum); HIGHEST keeps the f32 log-decays exact enough
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    u_idx = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    L = jax.lax.dot_general(
        (u_idx <= t_idx).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )                                            # [cs, C]
    Lx = L - lw                                  # exclusive  L_{t-1}

    # inter-chunk contribution through the carried state (MXU matmul)
    r_dec = r * jnp.exp(Lx)
    inter = jax.lax.dot_general(
        r_dec, s_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # [cs, C]

    # intra-chunk pairwise scores (strictly lower-triangular in t, u),
    # accumulated one channel at a time so every operand stays 2-D
    tri = u_idx < t_idx                          # u < t
    LT, kT = L.T, k.T                            # [C, cs]
    att = jnp.zeros((cs, cs), jnp.float32)
    for i in range(C):
        rel = jnp.where(tri, Lx[:, i : i + 1] - LT[i : i + 1, :], -jnp.inf)
        att = att + (r[:, i : i + 1] * jnp.exp(rel)) * kT[i : i + 1, :]
    out = inter + jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)           # [cs, 1]
    out = out + diag * v

    # state update: S ← exp(L_T) ∘ S + Σ_u exp(L_T − L_u) k_u ⊗ v_u
    decay_all = jnp.exp(L[cs - 1 :, :] - L)      # [cs, C] (≤ 1)
    s_new = jnp.exp(LT[:, cs - 1 :]) * s_ref[...] + jax.lax.dot_general(
        decay_all * k, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_ref[...] = s_new
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(
    r: jax.Array,  # [B, S, H, C]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # [B, S, H, C] decay in (0, 1)
    u: jax.Array,  # [H, C]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pallas WKV; matches :func:`repro.kernels.ref.wkv6_ref` (zero initial state)."""
    B, S, H, C = r.shape
    cs = min(chunk, S)
    assert S % cs == 0, (S, cs)
    nc = S // cs

    logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))
    kernel = functools.partial(_wkv_kernel, cs=cs, C=C)

    # head-major layout: each block's trailing (cs, C) dims are tile-aligned
    rt, kt, vt, lwt = (jnp.swapaxes(x, 1, 2) for x in (r, k, v, logw))  # [B, H, S, C]
    seq_block = pl.BlockSpec((1, 1, cs, C), lambda b, h, ic: (b, h, ic, 0))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            seq_block,
            seq_block,
            seq_block,
            seq_block,
            pl.BlockSpec((1, 1, C), lambda b, h, ic: (h, 0, 0)),
        ],
        out_specs=seq_block,
        out_shape=jax.ShapeDtypeStruct((B, H, S, C), r.dtype),
        scratch_shapes=[pltpu.VMEM((C, C), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, lwt, u[:, None, :])
    return jnp.swapaxes(out, 1, 2)  # [B, S, H, C]
