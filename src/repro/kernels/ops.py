"""Dispatch layer: Pallas kernels ⇄ pure-jnp reference paths.

Models call these wrappers; ``use_pallas`` (from the ModelConfig) selects the
TPU kernels, otherwise the chunked pure-jnp twins in :mod:`repro.models` run
(CPU dry-runs, oracles).  The kernels compile to Mosaic for the TPU; only
the CPU kernel tests run them in interpret mode, by passing ``interpret=True``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .mamba_scan import mamba_scan_pallas
from .rwkv6_scan import wkv6_pallas

__all__ = ["attention", "wkv6", "mamba_scan"]


def attention(
    q, k, v, *, causal=True, window=0, logit_softcap=0.0,
    chunk_q=512, chunk_kv=1024, q_offset=0, use_pallas=False,
):
    """[B,S,H,hd] × [B,T,Kv,hd]² → [B,S,H,hd]."""
    if use_pallas:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
            block_q=chunk_q, block_kv=chunk_kv, q_offset=q_offset,
        )
    from repro.models.attention import flash_attention

    return flash_attention(
        q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
        chunk_q=chunk_q, chunk_kv=chunk_kv, q_offset=q_offset,
    )


def wkv6(r, k, v, w, u, *, chunk=128, s0=None, use_pallas=False):
    """RWKV-6 recurrence.  Pallas path requires zero initial state."""
    if use_pallas and s0 is None:
        out = wkv6_pallas(r, k, v, w, u, chunk=chunk)
        return out, None
    from repro.models.rwkv6 import wkv_chunked

    return wkv_chunked(r, k, v, w, u, chunk=chunk, s0=s0)


def mamba_scan(u, delta, A, Bmat, Cmat, *, chunk=128, h0=None, use_pallas=False):
    """Selective scan.  Pallas path requires zero initial state."""
    if use_pallas and h0 is None:
        y = mamba_scan_pallas(u, delta, A, Bmat, Cmat, chunk=chunk)
        return y, None
    from repro.models.mamba import ssm_chunked_scan

    return ssm_chunked_scan(u, delta, A, Bmat, Cmat, chunk=chunk, h0=h0)
