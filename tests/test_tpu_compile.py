"""Each Pallas kernel compiles for a TPU v5e at its model's real widths.

Nothing runs: the kernels are lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, which catches what interpret mode cannot —
unaligned blocks, primitives Mosaic has no lowering for, VMEM overuse.  The
topology is described inside a fixture, never at import, so only the worker
that runs these tests loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rwkv6_scan import wkv6_pallas

SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", ["stablelm-3b", "jamba-v0.1-52b"])
def test_flash_attention_compiles(one_chip, arch):
    """stablelm-3b: 32 MHA heads of 80; jamba: GQA 32/8 heads of 128."""
    cfg = ARCHS[arch]
    hd, bf = cfg.resolved_head_dim, jnp.bfloat16
    q = ((1, SEQ, cfg.n_heads, hd), bf)
    kv = ((1, SEQ, cfg.n_kv_heads, hd), bf)
    fn = lambda q, k, v: flash_attention_pallas(
        q, k, v, block_q=cfg.attn_chunk_q, block_kv=cfg.attn_chunk_kv
    )
    assert "tpu_custom_call" in _compile_text(fn, [q, kv, kv], one_chip)


def test_wkv6_compiles(one_chip):
    """rwkv6-7b: 64 heads of 64 channels, chunked at the config's ssm_chunk."""
    cfg = ARCHS["rwkv6-7b"]
    C = cfg.rwkv.head_dim
    H = cfg.d_model // C
    x = ((1, SEQ, H, C), jnp.bfloat16)
    fn = lambda r, k, v, w, u: wkv6_pallas(r, k, v, w, u, chunk=cfg.ssm_chunk)
    text = _compile_text(fn, [x, x, x, x, ((H, C), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text


def test_mamba_scan_compiles(one_chip):
    """jamba: d_inner = 2 × 4096, d_state 16, chunked at the config's ssm_chunk."""
    cfg = ARCHS["jamba-v0.1-52b"]
    di, ds = cfg.mamba.expand * cfg.d_model, cfg.mamba.d_state
    bf = jnp.bfloat16
    shapes = [
        ((1, SEQ, di), bf),
        ((1, SEQ, di), bf),
        ((di, ds), jnp.float32),
        ((1, SEQ, ds), bf),
        ((1, SEQ, ds), bf),
    ]
    fn = lambda u, d, A, B, C: mamba_scan_pallas(u, d, A, B, C, chunk=cfg.ssm_chunk)
    assert "tpu_custom_call" in _compile_text(fn, shapes, one_chip)
