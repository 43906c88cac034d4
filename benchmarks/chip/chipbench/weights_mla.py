"""Seeded weights of a DeepSeek-V3-style configuration, in the parameter
layout the program and :mod:`chipbench.reference_mla` read.

As :mod:`chipbench.weights`: one jitted call makes every leaf on the device
in float32; matrices are normal with std 1/sqrt(fan-in) (each output
projection 1/sqrt(heads × value head size), embedding tables 0.02), norm
scales ones, and the router's correction bias starts at zero.  Each leaf
draws from its own key, folded from the seed by the leaf's position.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .weights import _nest


def layout(c: Dict[str, Any]):
    """{path: (shape, init)} with init one of ('normal', std), ('ones',), ('zeros',)."""
    D, V, H = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    R, nope, rope, dv = c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_lead = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_lead
    E, held, F = c["n_routed_experts"] * c["ep_size"], c["n_routed_experts"], c["moe_intermediate_size"]
    Fs = c["n_shared_experts"] * F
    normal = lambda fan_in: ("normal", 1 / math.sqrt(fan_in))
    out = {
        "embed/table": ((V, D), ("normal", 0.02)),
        "lm_head/table": ((V, D), ("normal", 0.02)),
        "final_norm/scale": ((D,), ("ones",)),
    }

    def block(prefix, lead):
        out[f"{prefix}/norm1/scale"] = ((*lead, D), ("ones",))
        out[f"{prefix}/norm2/scale"] = ((*lead, D), ("ones",))
        m = f"{prefix}/mixer"
        out[f"{m}/wq/w"] = ((*lead, D, H, nope + rope), normal(D))
        out[f"{m}/wkv_a/w"] = ((*lead, D, R + rope), normal(D))
        out[f"{m}/kv_norm/scale"] = ((*lead, R), ("ones",))
        out[f"{m}/wkv_b/w"] = ((*lead, R, H, nope + dv), normal(R))
        out[f"{m}/wo/w"] = ((*lead, H, dv, D), normal(H * dv))

    def mlp(prefix, lead, width):
        out[f"{prefix}/wi_gate/w"] = ((*lead, D, width), normal(D))
        out[f"{prefix}/wi_up/w"] = ((*lead, D, width), normal(D))
        out[f"{prefix}/wo/w"] = ((*lead, width, D), normal(width))

    for i in range(n_lead):
        block(f"lead/layer{i}", ())
        mlp(f"lead/layer{i}/ffn", (), c["intermediate_size"])
    u, L = "units/pos0", (n_moe,)
    block(u, L)
    out[f"{u}/ffn/router/w"] = ((*L, D, E), normal(D))
    out[f"{u}/ffn/router_bias"] = ((*L, E), ("zeros",))
    out[f"{u}/ffn/w_gate/w"] = ((*L, held, D, F), normal(D))
    out[f"{u}/ffn/w_up/w"] = ((*L, held, D, F), normal(D))
    out[f"{u}/ffn/w_down/w"] = ((*L, held, F, D), normal(F))
    mlp(f"{u}/ffn/shared", L, Fs)
    return out


def make_params(c: Dict[str, Any], key):
    """The parameter tree (traceable; jit it)."""
    flat = {}
    for i, (path, (shape, init)) in enumerate(sorted(layout(c).items())):
        if init[0] == "normal":
            flat[path] = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * init[1]
        elif init[0] == "ones":
            flat[path] = jnp.ones(shape, jnp.float32)
        else:
            flat[path] = jnp.zeros(shape, jnp.float32)
    return _nest(flat)


def abstract_params(c: Dict[str, Any]):
    return jax.eval_shape(lambda k: make_params(c, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
