"""Trainer: the fault-tolerant training loop.

Responsibilities (large-scale runnability):

- drive ``build_train_step`` over the sharded data pipeline;
- **checkpoint/restart** through SCISPACE (local-write + MEU by default):
  periodic saves, and on (injectable) failure the loop restores the latest
  published checkpoint found via SDS discovery and replays from there —
  the data pipeline is stateless, so replay is exact;
- **elastic re-meshing**: ``reshard(new_mesh)`` rebuilds the step function
  and re-places the state; combined with reshard-on-load restore this
  covers pod loss/gain;
- **straggler mitigation** hooks: per-host step times feed the
  :class:`~repro.data.pipeline.WorkStealingBalancer`.

The loop is deliberately synchronous-SPMD (one jit per step) — the shape a
real multi-pod JAX deployment has; fault events are modeled as exceptions
raised by an injectable ``fault_hook`` because a CPU container cannot kill
real TPU workers.  Only that injected :class:`NodeFailure` triggers a
restore: any other error (a device OOM, a runtime fault) propagates.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.data.pipeline import ShardedPipeline, WorkStealingBalancer
from repro.distributed.sharding import batch_shardings
from repro.optim.adamw import AdamW
from repro.train.checkpoint import CheckpointManager
from repro.train.step import (
    build_train_step,
    init_state,
    init_state_abstract,
    shard_state,
    state_shardings,
)
from repro.train.telemetry import TELEMETRY, span

__all__ = ["Trainer", "TrainerConfig", "FaultInjector", "NodeFailure"]


class NodeFailure(Exception):
    """A modeled node loss, raised by a ``fault_hook``; the loop restores."""


class FaultInjector:
    """Deterministic failure schedule for restart tests: fail at given steps."""

    def __init__(self, fail_at: Optional[List[int]] = None):
        self.fail_at = set(fail_at or [])
        self.fired: List[int] = []

    def __call__(self, step: int) -> None:
        if step in self.fail_at and step not in self.fired:
            self.fired.append(step)
            raise NodeFailure(f"injected node failure at step {step}")


@dataclass
class TrainerConfig:
    microbatches: int = 1
    loss_chunk: int = 256
    cross_pod: str = "auto"
    ckpt_every: int = 0           # 0 ⇒ no checkpointing
    max_restarts: int = 3
    log_every: int = 10


class Trainer:
    def __init__(
        self,
        model,
        optimizer: AdamW,
        mesh,
        pipeline: ShardedPipeline,
        cfg: TrainerConfig = TrainerConfig(),
        *,
        ckpt: Optional[CheckpointManager] = None,
        fault_hook: Optional[Callable[[int], None]] = None,
        seed: int = 0,
    ):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.pipeline = pipeline
        self.cfg = cfg
        self.ckpt = ckpt
        self.fault_hook = fault_hook
        self.seed = seed
        self._n_pods = mesh.shape.get("pod", 0) if cfg.cross_pod != "auto" else 0
        self._abstract = init_state_abstract(model, optimizer, n_pods=self._n_pods)
        self.shardings = state_shardings(self._abstract, mesh)
        self.state = self._init_state()
        self.step_fn = self._build()
        self.metrics_log: List[Dict[str, float]] = []
        self.balancer: Optional[WorkStealingBalancer] = None

    def _init_state(self):
        """Fresh state from ``seed``, created already sharded on the mesh."""
        init = functools.partial(init_state, self.model, self.optimizer, n_pods=self._n_pods)
        return jax.jit(init, out_shardings=self.shardings)(jax.random.PRNGKey(self.seed))

    def _build(self):
        return build_train_step(
            self.model,
            self.optimizer,
            self.mesh,
            microbatches=self.cfg.microbatches,
            loss_chunk=self.cfg.loss_chunk,
            cross_pod=self.cfg.cross_pod,
        )

    # -- elastic re-meshing ---------------------------------------------------
    def reshard(self, new_mesh) -> None:
        """Move training to a different mesh (pod loss/gain)."""
        host_state = jax.tree.map(np.asarray, self.state)
        self.mesh = new_mesh
        self.shardings = state_shardings(self._abstract, new_mesh)
        self.state = shard_state(host_state, self.shardings)
        self.step_fn = self._build()

    # -- data placement --------------------------------------------------------
    def _device_batch(self, batch_np: Dict[str, np.ndarray]):
        abstract = jax.eval_shape(lambda: batch_np)
        sh = batch_shardings(abstract, self.mesh)
        return jax.tree.map(jax.device_put, dict(batch_np), sh)

    # -- the loop ----------------------------------------------------------------
    def current_step(self) -> int:
        return int(self.state["step"])

    def _step(self, step: int) -> None:
        """Take step ``step + 1`` and save after it when due; its rows take
        their seconds from the spans (see repro.train.telemetry)."""
        if self.fault_hook is not None:
            self.fault_hook(step)
        with span("train.batch") as sp:
            batch_np = self.pipeline.batch_at(step)
            batch = self._device_batch(batch_np)
            if TELEMETRY.tracer.enabled:
                sp.tags = {"bytes": sum(x.nbytes for x in jax.tree.leaves(batch_np))}
        with span("train.step") as sp:
            self.state, metrics = jax.block_until_ready(self.step_fn(self.state, batch))
        if TELEMETRY.tracer.enabled and "moe" in metrics:
            sp.tags = {f"moe_{k}": int(v) for k, v in metrics["moe"].items()}
        dt = sp.end - sp.start
        if self.balancer is not None:
            self.balancer.report(self.pipeline.dp_rank, dt)
        row = {
            "step": step + 1,
            "loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "lr": float(metrics["lr"]),
            "seconds": dt,
        }
        self.metrics_log.append(row)
        if self.ckpt and self.cfg.ckpt_every and (step + 1) % self.cfg.ckpt_every == 0:
            with span("ckpt.d2h") as sp:
                host = jax.tree.map(np.asarray, self.state)
            if TELEMETRY.tracer.enabled:
                sp.tags = {"bytes": sum(x.nbytes for x in jax.tree.leaves(host))}
            info = self.ckpt.save(host, step + 1)
            del host
            # the stall: device→host copy plus the whole save
            self.metrics_log.append(
                {"step": step + 1, "event": "save", "seconds": sp.end - sp.start + info["total_s"], **info}
            )

    def run(self, n_steps: int) -> Dict[str, Any]:
        """Run to global step ``n_steps`` with restart-on-failure.

        Each loop iteration is a ``train.iter`` span."""
        restarts = 0
        with jax.set_mesh(self.mesh):
            step = self.current_step()
            while step < n_steps:
                with span("train.iter", step=step + 1):
                    try:
                        self._step(step)
                    except NodeFailure as exc:
                        # restore the latest published checkpoint
                        restarts += 1
                        if restarts > self.cfg.max_restarts or self.ckpt is None:
                            raise
                        latest = self.ckpt.latest_step()
                        if latest is None:
                            # no checkpoint yet: restart from scratch
                            self.state = self._init_state()
                        else:
                            self.state = self.ckpt.restore(
                                self._abstract, latest, shardings=self.shardings
                            )
                        self.metrics_log.append(
                            {"step": self.current_step(), "event": f"restart({exc})"}
                        )
                    step = self.current_step()
        return {
            "final_step": step,
            "restarts": restarts,
            "final_loss": next(
                (m["loss"] for m in reversed(self.metrics_log) if "loss" in m), None
            ),
        }
