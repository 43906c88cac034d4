"""Pallas TPU kernels for the framework's compute hot-spots.

The paper (SCISPACE) has no kernel-level contribution — these kernels exist
because the *framework's* model substrate needs them on TPU (DESIGN.md §6):

- :mod:`.flash_attention` — fused online-softmax attention (all attn archs)
- :mod:`.rwkv6_scan`      — chunked WKV recurrence (RWKV-6 "Finch")
- :mod:`.mamba_scan`      — chunked selective scan (Jamba's Mamba mixer)

Each kernel has a pure-jnp oracle in :mod:`.ref` and a jit'd dispatch wrapper
in :mod:`.ops`; tests sweep shapes/dtypes and assert_allclose kernel-vs-ref
in interpret mode on the CPU, and ``tests/test_tpu_compile.py`` compiles each
kernel at its real widths for a described TPU v5e.
"""

from .ops import attention, mamba_scan, wkv6

__all__ = ["attention", "mamba_scan", "wkv6"]
