"""Train-step builder: jit + GSPMD baseline, manual-pod hierarchical variants.

Three cross-pod modes (DESIGN.md §4):

- ``auto`` (baseline) — one ``jax.jit`` over the whole mesh; GSPMD inserts
  the gradient reduction (fused f32 all-reduce over pod×data).  This is the
  paper-faithful-substrate baseline every dry-run cell uses.
- ``manual`` — the step body runs under ``shard_map`` manual over ``pod``
  (auto over data/model): GSPMD reduces within the pod, and the cross-pod
  hop is an explicit f32 pmean.  Hierarchical: the DCN sees pod-local
  *already-averaged* gradients once, never raw per-chip traffic.
- ``compressed`` — like ``manual`` but the pod hop is int8 with error
  feedback (4× less DCN traffic; :mod:`repro.optim.compression`), the
  SCISPACE move: full-fidelity data stays local, a compact synchronization
  crosses the slow link.

Microbatch gradient accumulation runs as ``lax.scan`` so activation memory
is bounded by one microbatch; with remat inside the model's unit scan this
is the standard memory-bounded training configuration.

State pytree: {params, opt_state{mu,nu,count}, step, [ef]}.  All entries
inherit parameter shardings leaf-for-leaf; ``ef`` carries a leading pod dim.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.collectives import hierarchical_grad_mean
from repro.distributed.sharding import batch_shardings, batch_spec, param_shardings
from repro.optim.adamw import AdamW

__all__ = ["TrainState", "init_state", "state_shardings", "build_train_step"]

TrainState = Dict[str, Any]


def init_state(model, optimizer: AdamW, key, *, n_pods: int = 0) -> TrainState:
    params = model.init(key)
    state: TrainState = {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if n_pods:
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros((n_pods, *p.shape), jnp.float32), params
        )
    return state


def init_state_abstract(model, optimizer: AdamW, *, n_pods: int = 0):
    """ShapeDtypeStruct state (dry-run: no allocation)."""
    return jax.eval_shape(
        lambda k: init_state(model, optimizer, k, n_pods=n_pods),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )


def state_shardings(state_abstract, mesh: Mesh, *, fsdp: bool = False):
    """Params/mu/nu share the parameter sharding; ef adds a leading pod dim."""
    p_sh = param_shardings(state_abstract["params"], mesh, fsdp=fsdp)
    out = {
        "params": p_sh,
        "opt_state": {
            "mu": p_sh,
            "nu": p_sh,
            "count": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }
    if "ef" in state_abstract:
        def ef_shard(s):
            # [n_pods, *param_shape]: pod-sharded on dim 0, param spec shifted
            return NamedSharding(mesh, P("pod", *s.spec))
        out["ef"] = jax.tree.map(ef_shard, p_sh)
    return out


def _microbatched_grads(model, params, batch, microbatches: int, loss_chunk: int):
    """Mean loss/grads over ``microbatches`` sequential slices (lax.scan)."""
    loss_fn = lambda p, b: model.train_loss(p, b, loss_chunk=loss_chunk)

    if microbatches == 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    from repro.distributed.vma import vary

    mb = jax.tree.map(
        lambda x: x.reshape(microbatches, x.shape[0] // microbatches, *x.shape[1:]),
        batch,
    )
    zero_grads = vary(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def acc_step(carry, one):
        g_acc, l_acc = carry
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, one)
        g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
        return (g_acc, l_acc + loss), None

    (g_acc, l_acc), _ = jax.lax.scan(
        acc_step, (zero_grads, vary(jnp.zeros((), jnp.float32))), mb
    )
    inv = 1.0 / microbatches
    grads = jax.tree.map(lambda g: g * inv, g_acc)
    loss = l_acc * inv
    return loss, {"loss": loss}, grads


def build_train_step(
    model,
    optimizer: AdamW,
    mesh: Mesh,
    *,
    microbatches: int = 1,
    loss_chunk: int = 256,
    cross_pod: str = "auto",  # 'auto' | 'manual' | 'compressed'
    donate: bool = True,
):
    """Returns (jitted train_step, state_shardings_fn)."""
    assert cross_pod in ("auto", "manual", "compressed"), cross_pod
    has_pod = "pod" in mesh.axis_names
    if cross_pod != "auto":
        assert has_pod, "manual/compressed cross-pod modes need a pod axis"

    def body(state: TrainState, batch) -> Tuple[TrainState, Dict[str, jax.Array]]:
        loss, metrics, grads = _microbatched_grads(
            model, state["params"], batch, microbatches, loss_chunk
        )
        ef = state.get("ef")
        if cross_pod != "auto":
            grads, ef = hierarchical_grad_mean(
                grads, ef, compress=(cross_pod == "compressed")
            )
            loss = jax.lax.pmean(loss, "pod")
        new_params, new_opt, stats = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        new_state: TrainState = {
            "params": new_params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
        }
        if "ef" in state:
            new_state["ef"] = ef if cross_pod == "compressed" else state["ef"]
        out_metrics = {"loss": loss, **stats}
        return new_state, out_metrics

    if cross_pod == "auto":
        step_fn = body
    else:
        # manual over pod, auto over data/model.  Specs describe only the
        # pod axis: batch and ef are pod-split on dim 0, everything else is
        # pod-replicated (vma checking verifies the reduction discipline).
        def specs_of(state_abs, batch_abs):
            st = {
                "params": jax.tree.map(lambda _: P(), state_abs["params"]),
                "opt_state": jax.tree.map(lambda _: P(), state_abs["opt_state"]),
                "step": P(),
            }
            if "ef" in state_abs:
                st["ef"] = jax.tree.map(lambda _: P("pod"), state_abs["ef"])
            bt = jax.tree.map(lambda _: P("pod"), batch_abs)
            return st, bt

        def body_manual(state, batch):
            from repro.distributed.vma import manual_axes

            with manual_axes("pod"):  # trace-time flag: scan carries pcast varying
                return body(state, batch)

        def step_fn(state, batch):
            st_specs, b_specs = specs_of(state, batch)
            out_specs = (st_specs, {"loss": P(), "grad_norm": P(), "lr": P()})
            return jax.shard_map(
                body_manual,
                mesh=mesh,
                in_specs=(st_specs, b_specs),
                out_specs=out_specs,
                axis_names={"pod"},
            )(state, batch)

    jit_kwargs: Dict[str, Any] = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    return jax.jit(step_fn, **jit_kwargs)


def shard_state(state: TrainState, shardings) -> TrainState:
    """device_put the state with its shardings (host → mesh)."""
    return jax.tree.map(jax.device_put, state, shardings)
