"""Fused flash attention for TPU (``pl.pallas_call`` + explicit BlockSpecs).

Layout and tiling
-----------------
Grid ``(B, H, nq, nk)`` with the KV index innermost — TPU grids iterate
sequentially, so the online-softmax running state (m, l, acc) lives in VMEM
scratch and persists across the ``nk`` steps of one (b, h, q-block).  Each
step streams one KV tile HBM→VMEM; the [bq, bk] score tile is produced on the
MXU and never leaves VMEM.  GQA is handled in the index maps: the K/V block
for query head ``h`` is fetched from KV head ``h // group_size``, so K/V are
never materialized at H heads.

Block shapes: ``(bq, head_dim)`` / ``(bk, head_dim)`` with bq/bk multiples of
128 in production (MXU-aligned); head_dim is the lane dimension.  VMEM
working set ≈ bq·hd (q) + 2·bk·hd (kv) + bq·bk (scores) + bq·hd (acc) floats
— for bq=bk=512, hd=128: ~1.9 MB, well inside the ~16 MB/core budget while
leaving room for double-buffered pipelining.

Masking: causal and sliding-window tiles that are provably empty are skipped
via ``pl.when`` on block indices (the compiler elides the DMA + compute), so
a 500k-token causal sweep does half the work of the rectangular grid and a
windowed sweep touches only O(S·window) tiles.

Softcap (Gemma2) is applied to the score tile before masking, matching
:func:`repro.kernels.ref.attention_ref`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_BIG_NEG = -1e30


def _attn_kernel(
    q_ref,    # [1, 1, bq, hd]
    k_ref,    # [1, 1, bk, hd]
    v_ref,    # [1, 1, bk, hd]
    o_ref,    # [1, 1, bq, hd]
    m_ref,    # VMEM [bq, 1]
    l_ref,    # VMEM [bq, 1]
    acc_ref,  # VMEM [bq, hd]
    *,
    scale: float,
    causal: bool,
    window: int,
    logit_softcap: float,
    q_offset: int,
    bq: int,
    bk: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _BIG_NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this tile's queries/keys
    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # tile-level skip: provably-empty tiles do no DMA-dependent compute
    first_q = q_offset + iq * bq            # smallest query position in tile
    last_q = first_q + bq - 1
    first_k = ik * bk
    last_k = first_k + bk - 1
    live = jnp.bool_(True)
    if causal:
        live &= first_k <= last_q           # some key at/below the diagonal
    if window and window > 0:
        live &= last_k > first_q - window   # some key inside the window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # [bq, bk]
        if logit_softcap and logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        ok = jnp.ones((bq, bk), dtype=bool)
        if causal:
            ok &= k_pos <= q_pos
        if window and window > 0:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, _BIG_NEG)

        m_prev = m_ref[...]                                 # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                              # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                      # [bq, 1]
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, ...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "logit_softcap", "block_q", "block_kv",
        "q_offset", "scale", "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, Kv, hd]
    v: jax.Array,  # [B, T, Kv, hd]
    *,
    causal: bool = True,
    window: int = 0,
    logit_softcap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    q_offset: int = 0,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas fused attention.  Shapes as in :func:`repro.kernels.ref.attention_ref`."""
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    assert H % Kv == 0, (H, Kv)
    G = H // Kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bq = min(block_q, S)
    bk = min(block_kv, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nq, nk = S // bq, T // bk

    # head-major layout so the (b, h) grid axes map to leading block dims
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, S, hd]
    kt = jnp.swapaxes(k, 1, 2)  # [B, Kv, T, hd]
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        logit_softcap=logit_softcap,
        q_offset=q_offset,
        bq=bq,
        bk=bk,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)  # [B, S, H, hd]
