"""Chunked Mamba-1 selective scan for TPU (``pl.pallas_call`` + BlockSpecs).

TPU adaptation (DESIGN.md §6): the CUDA kernel assigns one thread per channel
and scans time sequentially in registers.  On TPU the equivalent is a grid
over ``(batch, d_inner blocks, time chunks)`` with the per-channel state
h ∈ ℝ^{ds×bd} held in VMEM scratch (channels on the 128-wide lane axis);
inside a chunk, a ``fori_loop`` advances time with fully-vectorized [ds, bd]
elementwise updates on the VPU while the chunk's inputs sit in VMEM.  The diagonal-A structure of Mamba-1 makes the
update elementwise (no MXU work is lost by not using it — there is no matmul
in the recurrence), and ``y_t = C_t · h_t`` is a ds-reduction fused into the
same loop.

decay/drive (``exp(Δ·A)``, ``Δ·u·B``) are computed *inside* the kernel from
the [bd]- and [ds]-shaped chunk inputs rather than materialized at
[B, S, di, ds] in HBM — an 8–16× traffic cut versus the naive lowering, which
is exactly what makes the attention-free archs memory-bound rather than
HBM-traffic-pathological on long contexts.

VMEM per step: chunk·(2·bd + 2·ds) input floats + bd·ds state + 3·chunk·bd
f32 row copies and out (chunk=128, bd=256, ds=16 → ~0.6 MB).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_scan_pallas"]


def _scan_kernel(
    u_ref,    # [1, cs, bd]
    d_ref,    # [1, cs, bd]   delta (softplus'd)
    A_ref,    # [ds, bd]      A transposed: channels on lanes
    b_ref,    # [1, ds, cs]   B transposed: time on lanes
    c_ref,    # [1, ds, cs]   C transposed
    y_ref,    # [1, cs, bd]
    h_ref,    # VMEM [ds, bd] running state
    dt_s,     # VMEM [cs, bd] f32 delta rows
    dtu_s,    # VMEM [cs, bd] f32 delta·u rows
    y_s,      # VMEM [cs, bd] f32 output rows
    *,
    cs: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt = d_ref[0].astype(jnp.float32)              # [cs, bd]
    dt_s[...] = dt
    dtu_s[...] = dt * u_ref[0].astype(jnp.float32)
    A = A_ref[...].astype(jnp.float32)             # [ds, bd]
    Bt = b_ref[0].astype(jnp.float32)              # [ds, cs]
    Ct = c_ref[0].astype(jnp.float32)              # [ds, cs]
    lane = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)

    def step(t, h):
        # row t of the f32 copies is read from the refs (Mosaic lowers no
        # dynamic value slicing); column t of B/C by a one-hot lane reduce
        dt_t = dt_s[pl.ds(t, 1), :]                                   # [1, bd]
        dtu_t = dtu_s[pl.ds(t, 1), :]                                 # [1, bd]
        b_t = jnp.sum(jnp.where(lane == t, Bt, 0.0), axis=1, keepdims=True)  # [ds, 1]
        c_t = jnp.sum(jnp.where(lane == t, Ct, 0.0), axis=1, keepdims=True)  # [ds, 1]
        h = jnp.exp(dt_t * A) * h + dtu_t * b_t                       # [ds, bd]
        y_s[pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=0, keepdims=True)
        return h

    h_ref[...] = jax.lax.fori_loop(0, cs, step, h_ref[...])
    y_ref[0] = y_s[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def mamba_scan_pallas(
    u: jax.Array,      # [B, S, di]
    delta: jax.Array,  # [B, S, di]
    A: jax.Array,      # [di, ds]
    Bmat: jax.Array,   # [B, S, ds]
    Cmat: jax.Array,   # [B, S, ds]
    *,
    chunk: int = 128,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Pallas selective scan; matches :func:`repro.kernels.ref.mamba_scan_ref`
    (zero initial state).  Returns y [B, S, di]."""
    B, S, di = u.shape
    ds = A.shape[1]
    cs = min(chunk, S)
    bd = min(block_d, di)
    assert S % cs == 0 and di % bd == 0, (S, cs, di, bd)
    nc = S // cs
    nd = di // bd

    kernel = functools.partial(_scan_kernel, cs=cs)
    row_block = pl.BlockSpec((1, cs, bd), lambda b, idd, ic: (b, ic, idd))
    col_block = pl.BlockSpec((1, ds, cs), lambda b, idd, ic: (b, 0, ic))

    y = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            row_block,
            row_block,
            pl.BlockSpec((ds, bd), lambda b, idd, ic: (0, idd)),
            col_block,
            col_block,
        ],
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((B, S, di), u.dtype),
        scratch_shapes=[
            pltpu.VMEM((ds, bd), jnp.float32),
            pltpu.VMEM((cs, bd), jnp.float32),
            pltpu.VMEM((cs, bd), jnp.float32),
            pltpu.VMEM((cs, bd), jnp.float32),
        ],
        interpret=interpret,
    )(u, delta, A.T, jnp.swapaxes(Bmat, 1, 2), jnp.swapaxes(Cmat, 1, 2))
    return y
