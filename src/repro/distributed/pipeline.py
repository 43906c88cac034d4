"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Off by default on the assigned production meshes (they expose pod/data/model
only), but required for 1000+-node deployments where a single model's layers
exceed one pod — the launcher accepts ``--mesh ...,stage=K``.

Mechanics (pure ``shard_map`` + ``lax.ppermute``):

- layer-stacked params are sharded over ``stage`` on their leading (unit)
  dimension — each stage holds n_units/K contiguous units;
- the microbatched input circulates: each of ``M + K - 1`` pipeline ticks
  runs the local stage on its current microbatch and ppermutes activations
  to the next stage (bubble fraction (K-1)/(M+K-1), the GPipe schedule);
- the final stage scatters its outputs back to microbatch order.

This module is deliberately self-contained (own dry-run test) rather than
threaded through every model: the assigned meshes keep it disabled, and the
cost model in EXPERIMENTS.md §Roofline covers the non-PP configuration.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipelined_forward"]


def pipelined_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    n_microbatches: int,
    stage_axis: str = "stage",
):
    """Build a pipelined forward: (stage_params, x [M·b, ...]) → y.

    ``stage_fn(params_for_stage, x_mb)`` applies one stage's layers to one
    microbatch.  ``stage_params`` leaves must have a leading dim divisible
    by the stage count (units sharded contiguously).
    """
    K = mesh.shape[stage_axis]
    M = n_microbatches
    assert M >= 1

    def run(stage_params, x):
        # x arrives stage-sharded on dim 0 (shard_map slices it); only the
        # first stage's shard is real input, later stages start from zeros.
        stage = jax.lax.axis_index(stage_axis)
        mb = x.reshape(M, -1, *x.shape[1:])          # [M, b, ...]
        buf = jnp.zeros_like(mb[0])                  # current activation
        outs = jnp.zeros_like(mb)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if still in range)
            take = jnp.clip(t, 0, M - 1)
            injected = jnp.where(stage == 0, mb[take], buf)
            live = (stage <= t) & (t - stage < M)
            y = stage_fn(stage_params, injected)
            y = jnp.where(live, y, injected)
            # last stage banks its finished microbatch
            done_idx = jnp.clip(t - (K - 1), 0, M - 1)
            bank = (stage == K - 1) & (t >= K - 1)
            outs = jax.lax.cond(
                bank,
                lambda o: o.at[done_idx].set(y),
                lambda o: o,
                outs,
            )
            # circulate activations forward one stage
            perm = [(i, (i + 1) % K) for i in range(K)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, M + K - 1, tick, (buf, outs))
        # only the last stage holds real outputs; share them with all stages
        outs = jax.lax.psum(
            jnp.where(stage == K - 1, outs, jnp.zeros_like(outs)), stage_axis
        )
        return outs.reshape(-1, *x.shape[1:])

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        axis_names={stage_axis},
        check_vma=False,
    )
