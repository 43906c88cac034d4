"""What every training job shares: its result, its checks, the first steps
through the trainer, and the plain reference's readings they are compared with.

A job (``jobs/<job>.py``) returns a :class:`Result`; the harness turns it
into the result line.
"""

from __future__ import annotations

import gc
import hashlib
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import data as data_mod
from . import reference, weights


@dataclass
class Check:
    name: str
    value: float
    limit: float
    note: str = ""

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Result:
    e2e: Dict[str, float] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    checks: List[Check] = field(default_factory=list)
    steps: List[Dict[str, float]] = field(default_factory=list)   # trainer rows in the window
    saves: List[Dict[str, float]] = field(default_factory=list)   # save rows in the window
    step_flops: float = 0.0
    memory_peak_bytes: int = 0


def digests(tree) -> List[str]:
    """One blake2b digest per leaf, copied to the host one leaf at a time."""
    return [
        hashlib.blake2b(np.ascontiguousarray(np.asarray(leaf)).view(np.uint8)).hexdigest()
        for leaf in jax.tree.leaves(tree)
    ]


def memory_peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices())


def window_rows(tr, since: int):
    """The trainer's step rows and save rows logged since row ``since``."""
    new = tr.metrics_log[since:]
    return [r for r in new if "loss" in r], [r for r in new if r.get("event") == "save"]


def _gap(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def _worst_leaf(prog, ref, include=None) -> Tuple[float, int]:
    """Largest |‖prog‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref leaf‖), and its leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if include is None else np.asarray(include)
    med = float(np.median(ref[keep]))
    gaps = np.where(keep, np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30), 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


# ---------------------------------------------------------------------------
# the reference's three steps
# ---------------------------------------------------------------------------

#: A leaf whose reference gradient is under this share of the median leaf's
#: moves by round-off alone (a key's bias under softmax): its change is not compared.
STILL_LEAF = 1e-3


def reference_readings(c, t, seed: int, n_steps: int, *, cast=None, rows=None) -> Dict[str, Any]:
    """Losses, step-1 gradient norms and the change after ``n_steps`` of the
    plain reference from the seed's weights on the traffic's batches.
    ``rows`` keeps only those rows of each batch (a planted fault)."""
    key = weights.key_for(seed)
    make = jax.jit(lambda k: weights.make_params(c, k))
    params = make(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu, count = zeros(params), zeros(params), jnp.zeros((), jnp.int32)
    step = reference.make_step(c, t["optimizer"], cast)
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
    return {"losses": losses, "grad_norm": gnorm, "grad_leaf": gleaf, "delta_leaf": delta}


def delta_norms(c):
    return jax.jit(lambda p, k: reference.leaf_norms(
        jax.tree.map(jnp.subtract, p, weights.make_params(c, k))))


def leaf_paths(c) -> List[str]:
    flat = jax.tree_util.tree_flatten_with_path(weights.abstract_params(c))[0]
    return ["/".join(str(k.key) for k in kp) for kp, _ in flat]


def compare_training(prog: Dict[str, Any], ref: Dict[str, Any], limits: Dict[str, float], paths) -> List[Check]:
    n = len(ref["losses"])
    loss_gap = max(_gap(p, r) for p, r in zip(prog["losses"][:n], ref["losses"]))
    grad_leaf, gi = _worst_leaf(prog["grad_leaf"], ref["grad_leaf"])
    moving = ref["grad_leaf"] >= STILL_LEAF * np.median(ref["grad_leaf"])
    upd_leaf, ui = _worst_leaf(prog["delta_leaf"], ref["delta_leaf"], moving)
    return [
        Check("loss", loss_gap, limits["loss"], f"steps 1-{n}, relative"),
        Check("grad_norm", _gap(prog["grad_norm"], ref["grad_norm"]), limits["grad_norm"],
              "step 1, global, before clipping"),
        Check("grad_leaf", grad_leaf, limits["grad_leaf"], f"step 1, worst leaf {paths[gi]}"),
        Check("update_leaf", upd_leaf, limits["update_leaf"],
              f"after step {n}, worst leaf {paths[ui]}, its reference gradient "
              f"{ref['grad_leaf'][ui] / np.median(ref['grad_leaf']):.3g} of the median leaf's; "
              f"{int((~moving).sum())} still leaves left out"),
    ]


def free(tr) -> None:
    """Drop the trainer's device state before the reference needs the chip."""
    tr.state = None
    gc.collect()


def window_over(run, w, cycles: int) -> bool:
    """One stop rule for every window: a traffic file that gives ``cycles``
    runs exactly that many (a save cycle or a resume is tens of seconds, and
    a first one in a process is slower than the next); any other runs whole
    cycles until ``--seconds`` have passed."""
    want = run.traffic.get("cycles")
    return cycles >= want if want else w.elapsed() >= run.seconds


def first_steps(tr, c, t, key, after_first=None) -> Dict[str, Any]:
    """Take the first ``reference_steps`` steps through the trainer's own
    call and feed; keep what the reference will be compared on: the losses,
    step 1's gradient as the optimizer got it (its first moment over 1 − b1)
    and the parameters' change."""
    tr.run(1)
    if after_first is not None:
        after_first()
    grad_leaf = np.asarray(jax.jit(reference.leaf_norms)(tr.state["opt_state"]["mu"]))
    grad_leaf = grad_leaf / (1 - t["optimizer"]["b1"])
    n = t["reference_steps"]
    tr.run(n)
    delta_leaf = np.asarray(delta_norms(c)(tr.state["params"], key))
    rows = [r for r in tr.metrics_log if "loss" in r][:n]
    return {"losses": [r["loss"] for r in rows], "grad_norm": rows[0]["grad_norm"],
            "grad_leaf": grad_leaf, "delta_leaf": delta_leaf}


def reference_checks(run, prog) -> List[Check]:
    c, t = run.config, run.traffic
    ref = reference_readings(c, t, run.seed, t["reference_steps"])
    return compare_training(prog, ref, run.limits, leaf_paths(c))
