"""What the ``train_mla`` job and its control share: the first steps through
the trainer, the plain reference's readings of the same steps, and the check.

The generic parts (the comparison of losses, gradient and update norms, the
result, the stop rule) are :mod:`chipbench.training`'s; this module swaps in
the DeepSeek-V3-style weights and reference, and adds one number: how many
of the correction bias's entries differ from the reference's after the
first steps.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import data as data_mod
from . import reference, reference_mla, weights, weights_mla
from .training import Check, compare_training


def _bias(params) -> np.ndarray:
    return np.asarray(params["units"]["pos0"]["ffn"]["router_bias"])


def delta_norms(c):
    return jax.jit(lambda p, k: reference.leaf_norms(
        jax.tree.map(jnp.subtract, p, weights_mla.make_params(c, k))))


def leaf_paths(c) -> List[str]:
    flat = jax.tree_util.tree_flatten_with_path(weights_mla.abstract_params(c))[0]
    return ["/".join(str(k.key) for k in kp) for kp, _ in flat]


def first_steps(tr, c, t, key) -> Dict[str, Any]:
    """As :func:`chipbench.training.first_steps`, and the bias after them."""
    tr.run(1)
    grad_leaf = np.asarray(jax.jit(reference.leaf_norms)(tr.state["opt_state"]["mu"]))
    grad_leaf = grad_leaf / (1 - t["optimizer"]["b1"])
    n = t["reference_steps"]
    tr.run(n)
    delta_leaf = np.asarray(delta_norms(c)(tr.state["params"], key))
    rows = [r for r in tr.metrics_log if "loss" in r][:n]
    return {"losses": [r["loss"] for r in rows], "grad_norm": rows[0]["grad_norm"],
            "grad_leaf": grad_leaf, "delta_leaf": delta_leaf, "bias": _bias(tr.state["params"])}


def reference_readings(c, t, seed: int, n_steps: int, *, cast=None, rows=None) -> Dict[str, Any]:
    """As :func:`chipbench.training.reference_readings`, on the plain MLA
    reference, and the bias after ``n_steps``."""
    key = weights.key_for(seed)
    params = jax.jit(lambda k: weights_mla.make_params(c, k))(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu, count = zeros(params), zeros(params), jnp.zeros((), jnp.int32)
    step = reference_mla.make_step(c, t["optimizer"], cast)
    batches = data_mod.TokenBatches(seed, t, c["vocab_size"])
    losses, gnorm, gleaf = [], None, None
    for i in range(n_steps):
        b = batches.batch_at(i)
        tok, tgt = b["tokens"], b["targets"]
        if rows is not None:
            tok, tgt = tok[rows], tgt[rows]
        params, mu, nu, count, value, gn, gl = step(params, mu, nu, count, jnp.asarray(tok), jnp.asarray(tgt))
        losses.append(float(value))
        if i == 0:
            gnorm, gleaf = float(gn), np.asarray(gl)
    del mu, nu
    delta = np.asarray(delta_norms(c)(params, key))
    return {"losses": losses, "grad_norm": gnorm, "grad_leaf": gleaf, "delta_leaf": delta,
            "bias": _bias(params)}


def compare(prog: Dict[str, Any], ref: Dict[str, Any], limits: Dict[str, float], c) -> List[Check]:
    """The generic check, and ``bias_update``: the share of the correction
    bias's entries that differ from the reference's by more than half of one
    step's move (a choice counted on the other side of an expert's mean load)."""
    checks = compare_training(prog, ref, limits, leaf_paths(c))
    gamma = c["bias_update_speed"]
    differ = np.abs(np.asarray(prog["bias"]) - np.asarray(ref["bias"])) > gamma / 2
    checks.append(Check("bias_update", float(differ.mean()), limits["bias_update"],
                        f"{int(differ.sum())} of {differ.size} entries after step {len(ref['losses'])}"))
    return checks


def reference_checks(run, prog) -> List[Check]:
    c, t = run.config, run.traffic
    ref = reference_readings(c, t, run.seed, t["reference_steps"])
    return compare(prog, ref, run.limits, c)
