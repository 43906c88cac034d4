"""Seeded weights in the parameter layout the reference reads.

One jitted call makes every leaf on the device, in float32 (the type the
trainer keeps its master weights in): matrices are normal with std
1/sqrt(fan-in) (the output projection 1/sqrt(heads × head size), embedding
tables 0.02), norm scales are ones and biases zeros.  Each leaf draws from
its own key, folded from the seed by the leaf's position in the tree.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, also one wider than 32 bits."""
    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def layout(c: Dict[str, Any]):
    """{path: (shape, init)} with init one of ('normal', std), ('ones',), ('zeros',)."""
    D, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, Kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    out = {
        "embed/table": ((V, D), ("normal", 0.02)),
        "lm_head/table": ((V, D), ("normal", 0.02)),
    }

    def norm(prefix, dim, lead=()):
        out[f"{prefix}/scale"] = ((*lead, dim), ("ones",))
        if c["norm"] == "layernorm":
            out[f"{prefix}/bias"] = ((*lead, dim), ("zeros",))

    norm("final_norm", D)
    u = "units/pos0"
    norm(f"{u}/norm1", D, (L,))
    norm(f"{u}/norm2", D, (L,))
    for name, heads in (("wq", H), ("wk", Kv), ("wv", Kv)):
        out[f"{u}/mixer/{name}/w"] = ((L, D, heads, hd), ("normal", 1 / math.sqrt(D)))
        if c["use_qkv_bias"]:
            out[f"{u}/mixer/{name}/b"] = ((L, heads, hd), ("zeros",))
    out[f"{u}/mixer/wo/w"] = ((L, H, hd, D), ("normal", 1 / math.sqrt(H * hd)))
    if c.get("qk_norm"):
        out[f"{u}/mixer/q_norm/scale"] = ((L, hd), ("ones",))
        out[f"{u}/mixer/k_norm/scale"] = ((L, hd), ("ones",))
    F = c["intermediate_size"]
    if c.get("num_experts"):
        E = c["num_experts"]
        out[f"{u}/ffn/router/w"] = ((L, D, E), ("normal", 1 / math.sqrt(D)))
        out[f"{u}/ffn/w_gate/w"] = ((L, E, D, F), ("normal", 1 / math.sqrt(D)))
        out[f"{u}/ffn/w_up/w"] = ((L, E, D, F), ("normal", 1 / math.sqrt(D)))
        out[f"{u}/ffn/w_down/w"] = ((L, E, F, D), ("normal", 1 / math.sqrt(F)))
    else:
        out[f"{u}/ffn/wi_gate/w"] = ((L, D, F), ("normal", 1 / math.sqrt(D)))
        out[f"{u}/ffn/wi_up/w"] = ((L, D, F), ("normal", 1 / math.sqrt(D)))
        out[f"{u}/ffn/wo/w"] = ((L, F, D), ("normal", 1 / math.sqrt(F)))
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


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
