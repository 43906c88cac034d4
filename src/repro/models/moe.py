"""Mixture-of-Experts FFN with GShard-style dense dispatch (TPU-native).

Routing uses top-k gating with per-expert capacity; dispatch/combine are
one-hot einsums, the canonical TPU formulation: no gather/scatter in the hot
path, and under GSPMD the dispatch einsums lower to all-to-alls when experts
are sharded on the `model` axis and tokens on `data`.

Covers the MoE archs:
- OLMoE:  64 experts, top-8, tiny experts (d_ff=1024)
- Jamba:  16 experts, top-2 on alternating layers
- Llama4: 128 experts, top-1 + an always-on shared expert
- Moonlight (DeepSeek-V3): 64 experts, top-6, sigmoid scores with a
  choice-only correction bias, weights × 2.446, 2 shared experts, the
  sequence-wise balance loss

A layer may hold only some of the experts (``MoESpec.n_held`` from
``first_held``): the expert-parallel share of one chip.

The dispatch einsum costs 2·B·S·(E·C)·D FLOPs (E·C ≈ S·top_k·cf), which the
roofline's MODEL_FLOPS/HLO_FLOPs ratio makes visible as routing overhead.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, dense, init_dense

__all__ = ["init_moe", "moe_layer"]


def init_moe(key, cfg, *, param_dtype) -> Params:
    """Router over all ``n_experts``; expert stacks of the ``held`` experts only."""
    spec = cfg.moe
    d, f, e = cfg.d_model, spec.d_ff, spec.held
    keys = jax.random.split(key, 5)

    def expert_stack(k, shape, fan_in):
        w = jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)
        return w.astype(param_dtype)

    p: Params = {
        "router": init_dense(keys[0], d, (spec.n_experts,), param_dtype=param_dtype),
        "w_gate": {"w": expert_stack(keys[1], (e, d, f), d)},
        "w_up": {"w": expert_stack(keys[2], (e, d, f), d)},
        "w_down": {"w": expert_stack(keys[3], (e, f, d), f)},
    }
    if spec.bias_rate > 0.0:
        p["router_bias"] = jnp.zeros((spec.n_experts,), jnp.float32)
    if spec.n_shared:
        from .layers import init_mlp

        p["shared"] = init_mlp(keys[4], d, spec.n_shared * f, activation=cfg.activation,
                               param_dtype=param_dtype)
    return p


def _route(p: Params, x: jax.Array, spec) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (weights [B,S,K], expert ids [B,S,K], scores [B,S,E]), in f32.

    Softmax or sigmoid scores over every expert; the top k of the scores
    plus the correction bias (where the layer has one) are chosen, and their
    scores, renormalised and times ``routed_scale``, weight them."""
    logits = dense(p["router"], x, dtype=jnp.float32)  # fp32 routing
    if spec.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choose = scores + jax.lax.stop_gradient(p["router_bias"]) if "router_bias" in p else scores
    _, idx = jax.lax.top_k(choose, spec.top_k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / jnp.maximum(weights.sum(axis=-1, keepdims=True), 1e-9)
    if spec.routed_scale != 1.0:
        weights = weights * spec.routed_scale
    return weights, idx, scores


def _balance_loss(idx: jax.Array, scores: jax.Array, spec) -> jax.Array:
    E, K = spec.n_experts, spec.top_k
    if spec.balance == "seq":
        # DeepSeek-V3's sequence-wise loss, per row then averaged: with f_i the
        # share of the row's choices at expert i times E/K and P_i the row's
        # mean of the normalised scores, L = coef·Σ_i f_i P_i
        S = idx.shape[1]
        counts = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=(1, 2))  # [B, E]
        f = counts * (E / (K * S))
        P = (scores / scores.sum(axis=-1, keepdims=True)).mean(axis=1)
        return jnp.mean(jnp.sum(f * P, axis=-1)) * spec.load_balance_coef
    # Switch/GShard: E * Σ_e fraction_e * mean_prob_e
    assign1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    fraction = assign1.mean(axis=(0, 1))
    mean_prob = scores.mean(axis=(0, 1))
    return E * jnp.sum(fraction * mean_prob) * spec.load_balance_coef


def moe_layer(
    p: Params,
    x: jax.Array,  # [B, S, D]
    cfg,
    *,
    dtype,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (output [B,S,D], load-balance aux loss scalar, stats).

    ``stats`` holds ``load``, the choices at each of the router's experts
    [E], and three counters: ``routed``, the choices bound for the experts
    held here; ``kept``, those within capacity; ``slots``, the expert slots
    computed.  Capacity is counted over each row's whole sequence, at
    ``cfg.moe.capacity_factor``.
    """
    return _moe_dispatch(p, x, cfg, dtype=dtype, capacity_factor=cfg.moe.capacity_factor)


def _moe_dispatch(
    p: Params,
    x: jax.Array,  # [B, S, D]
    cfg,
    *,
    dtype,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Route over all experts; dispatch to and compute only the held ones.

    Expert parallelism: the router is replicated and each chip computes its
    held experts' part of the result, for the choices bound to them.  With
    every expert held this is the whole layer."""
    spec = cfg.moe
    B, S, D = x.shape
    E, K = spec.held, spec.top_k
    C = max(1, int(math.ceil(S * K * capacity_factor / spec.n_experts)))

    gates, idx, scores = _route(p, x, spec)
    aux = _balance_loss(idx, scores, spec)
    load = jax.nn.one_hot(idx, spec.n_experts, dtype=jnp.int32).sum(axis=(0, 1, 2))
    idx = idx - spec.first_held  # ids among the held experts; one_hot drops the rest

    # build dispatch (one-hot over capacity slots) and combine tensors
    dispatch = jnp.zeros((B, S, E, C), dtype=jnp.bool_)
    combine = jnp.zeros((B, S, E, C), dtype=jnp.float32)
    # slots already used per expert as we sweep the K choices
    used = jnp.zeros((B, E), dtype=jnp.int32)
    routed = jnp.zeros((), jnp.int32)
    for k in range(K):
        onehot_e = jax.nn.one_hot(idx[..., k], E, dtype=jnp.int32)  # [B,S,E]
        # position within each expert queue (exclusive cumsum along S) + carry
        pos_in_e = jnp.cumsum(onehot_e, axis=1) - onehot_e + used[:, None, :]
        within = (pos_in_e < C) & (onehot_e > 0)
        slot = jax.nn.one_hot(pos_in_e, C, dtype=jnp.float32) * within[..., None]
        dispatch = dispatch | (slot.astype(jnp.bool_) & (onehot_e > 0)[..., None])
        combine = combine + slot * onehot_e[..., None] * gates[..., k][..., None, None]
        used = used + jnp.sum(onehot_e * within.astype(jnp.int32), axis=1)
        routed = routed + jnp.sum(onehot_e)
    stats = {"load": load, "routed": routed, "kept": jnp.sum(used),
             "slots": jnp.asarray(B * E * C, jnp.int32)}

    # dispatch: gather expert inputs  [E, B, C, D]
    xd = x.astype(dtype)
    expert_in = jnp.einsum(
        "bsec,bsd->ebcd", dispatch.astype(dtype), xd, preferred_element_type=dtype
    )

    # per-expert FFN via expert-stacked weights
    wg = p["w_gate"]["w"].astype(dtype)
    wu = p["w_up"]["w"].astype(dtype)
    wd = p["w_down"]["w"].astype(dtype)
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", expert_in, wg)) * jnp.einsum(
            "ebcd,edf->ebcf", expert_in, wu
        )
    elif cfg.activation == "relu2":
        h = jnp.square(jax.nn.relu(jnp.einsum("ebcd,edf->ebcf", expert_in, wu)))
    else:
        h = jax.nn.gelu(jnp.einsum("ebcd,edf->ebcf", expert_in, wu), approximate=True)
    expert_out = jnp.einsum("ebcf,efd->ebcd", h, wd)

    # combine back to token order
    y = jnp.einsum(
        "bsec,ebcd->bsd", combine.astype(dtype), expert_out, preferred_element_type=dtype
    )

    if spec.n_shared:
        from .layers import mlp

        y = y + mlp(p["shared"], x, activation=cfg.activation, dtype=dtype)
    return y.astype(x.dtype), aux, stats
