"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas
from repro.kernels.ref import attention_ref, mamba_scan_ref, wkv6_ref
from repro.models.attention import flash_attention
from repro.models.mamba import ssm_chunked_scan
from repro.models.rwkv6 import wkv_chunked

TOL = {"float32": dict(atol=2e-5, rtol=2e-5), "bfloat16": dict(atol=2e-2, rtol=2e-2)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "B,S,T,H,Kv,hd,causal,window,cap,bq,bk",
    [
        (2, 64, 64, 4, 2, 16, True, 0, 0.0, 32, 32),
        (1, 128, 128, 4, 4, 32, True, 32, 50.0, 32, 64),
        (2, 64, 64, 8, 2, 16, False, 0, 0.0, 16, 32),
        (1, 96, 96, 2, 1, 8, True, 0, 30.0, 32, 32),
        (1, 64, 128, 4, 2, 16, False, 0, 0.0, 64, 32),  # cross-attn T != S
    ],
)
def test_flash_attention_vs_ref(B, S, T, H, Kv, hd, causal, window, cap, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(ks[0], (B, S, H, hd), dt)
    k = jax.random.normal(ks[1], (B, T, Kv, hd), dt)
    v = jax.random.normal(ks[2], (B, T, Kv, hd), dt)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_softcap=cap, block_q=bq, block_kv=bk,
        interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window, logit_softcap=cap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_jnp_chunked_attention_vs_ref(chunk):
    """The model's pure-jnp flash twin matches the naive oracle too."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    out = flash_attention(q, k, v, causal=True, chunk_q=chunk, chunk_kv=chunk)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,C,chunk", [(2, 64, 2, 16, 16), (1, 128, 4, 8, 32), (1, 32, 1, 32, 8)])
def test_wkv6_pallas_vs_ref(B, S, H, C, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r = jax.random.normal(ks[0], (B, S, H, C))
    k = jax.random.normal(ks[1], (B, S, H, C))
    v = jax.random.normal(ks[2], (B, S, H, C))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, C))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, C)) * 0.1
    out = wkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    ref, _ = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_wkv6_chunk_invariance_and_state_carry():
    """Chunked == sequential for any chunking; carried state continues a split."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, H, C = 1, 64, 2, 8
    r, k, v = (jax.random.normal(ks[i], (B, S, H, C)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, C))) * 0.4 + 0.5
    u = jax.random.normal(ks[4], (H, C)) * 0.1
    full, s_full = wkv_chunked(r, k, v, w, u, chunk=16)
    # split the sequence and carry the state across the cut
    h1, s1 = wkv_chunked(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, chunk=16)
    h2, s2 = wkv_chunked(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, chunk=16, s0=s1)
    np.testing.assert_allclose(jnp.concatenate([h1, h2], 1), full, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s2, s_full, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,S,di,ds,chunk,bd", [(2, 64, 32, 8, 16, 16), (1, 32, 64, 16, 8, 64), (1, 128, 16, 4, 32, 16)])
def test_mamba_pallas_vs_ref(B, S, di, ds, chunk, bd):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    u = jax.random.normal(ks[0], (B, S, di))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, ds))
    Cm = jax.random.normal(ks[4], (B, S, ds))
    y = mamba_scan_pallas(u, delta, A, Bm, Cm, chunk=chunk, block_d=bd, interpret=True)
    ref, _ = mamba_scan_ref(u, delta, A, Bm, Cm)
    np.testing.assert_allclose(y, ref, atol=1e-4, rtol=1e-4)


def test_mamba_chunked_state_carry():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, S, di, ds = 1, 64, 16, 8
    u = jax.random.normal(ks[0], (B, S, di))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, ds))
    Cm = jax.random.normal(ks[4], (B, S, ds))
    full, h_full = ssm_chunked_scan(u, delta, A, Bm, Cm, chunk=16)
    y1, h1 = ssm_chunked_scan(u[:, :32], delta[:, :32], A, Bm[:, :32], Cm[:, :32], chunk=16)
    y2, h2 = ssm_chunked_scan(u[:, 32:], delta[:, 32:], A, Bm[:, 32:], Cm[:, 32:], chunk=16, h0=h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), full, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h2, h_full, atol=1e-4, rtol=1e-4)
