"""AdamW + schedules + clipping, pure-pytree (no optax dependency).

The optimizer state is a pytree congruent with the params, so the same
sharding rules apply leaf-for-leaf (first/second moments inherit the
parameter's PartitionSpec) — optimizer state is fully sharded, never
replicated (ZeRO-style by construction, since params are already TP/EP
sharded and DP only replicates).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "AdamW", "cosine_schedule", "global_norm", "clip_by_global_norm"]


def cosine_schedule(
    peak_lr: float, *, warmup_steps: int, total_steps: int, final_frac: float = 0.1
) -> Callable[[jax.Array], jax.Array]:
    def schedule(step):
        step = step.astype(jnp.float32)
        warm = peak_lr * jnp.minimum(1.0, step / jnp.maximum(warmup_steps, 1))
        t = jnp.clip(
            (step - warmup_steps) / jnp.maximum(total_steps - warmup_steps, 1), 0.0, 1.0
        )
        cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
        return jnp.where(step < warmup_steps, warm, peak_lr * cos)

    return schedule


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype), tree), norm


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


class AdamW:
    """init(params) → state;  update(grads, state, params) → (new_params, new_state, stats)."""

    def __init__(self, cfg: AdamWConfig):
        self.cfg = cfg
        self.schedule = cosine_schedule(
            cfg.peak_lr, warmup_steps=cfg.warmup_steps, total_steps=cfg.total_steps
        )

    def init(self, params) -> Dict[str, Any]:
        zeros = lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), t)
        return {"mu": zeros(params), "nu": zeros(params), "count": jnp.zeros((), jnp.int32)}

    def update(self, grads, state, params, frozen=None) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
        """``frozen``: a pytree of bools like ``params``; a True leaf and its
        moments are returned as they were (state the model moves itself)."""
        cfg = self.cfg
        if frozen is None:
            frozen = jax.tree.map(lambda _: False, params)
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        lr = self.schedule(count)

        def moments(g, mu, nu, still):
            if still:
                return mu, nu
            g = g.astype(jnp.float32)
            mu2 = cfg.b1 * mu + (1 - cfg.b1) * g
            nu2 = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g)
            return mu2, nu2

        mus_nus = jax.tree.map(moments, grads, state["mu"], state["nu"], frozen)
        mu = jax.tree.map(lambda t: t[0], mus_nus, is_leaf=lambda t: isinstance(t, tuple))
        nu = jax.tree.map(lambda t: t[1], mus_nus, is_leaf=lambda t: isinstance(t, tuple))

        b1c = 1 - cfg.b1 ** cf
        b2c = 1 - cfg.b2 ** cf

        def step(p, m, v, still):
            if still:
                return p
            upd = (m / b1c) / (jnp.sqrt(v / b2c) + cfg.eps)
            upd = upd + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        new_params = jax.tree.map(step, params, mu, nu, frozen)
        new_state = {"mu": mu, "nu": nu, "count": count}
        return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
