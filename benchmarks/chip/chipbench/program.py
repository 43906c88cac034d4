"""The system under test, built from a configuration file and a traffic file.

The only module of the benchmark that imports the program (``repro``).  It
drives the normal path: :class:`~repro.train.Trainer` with
:func:`~repro.train.step.build_train_step`, :class:`~repro.train.CheckpointManager`
in native (LW+MEU) mode, and a :class:`~repro.core.Collaboration` of data
centers with their DTNs on the default in-memory channel.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

RUN = "chipbench"  # the checkpoint run's name in the workspace


def model_config(c: Dict[str, Any]):
    """The program's ModelConfig with the file's sizes."""
    from repro.configs import get_config
    from repro.configs.base import MoESpec

    base = get_config(c["program_arch"])
    kw = dict(
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        rope_fraction=c["partial_rotary_factor"],
        rope_theta=float(c["rope_theta"]),
        norm=c["norm"],
        qkv_bias=bool(c["use_qkv_bias"]),
        qk_norm=bool(c.get("qk_norm")),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"],
    )
    if c.get("num_experts"):
        kw["moe"] = MoESpec(
            n_experts=c["num_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff=c["intermediate_size"],
            capacity_factor=c["moe_capacity_factor"],
            load_balance_coef=c["router_aux_loss_coef"],
        )
    cfg = base.replace(**kw)
    if cfg.norm == "rmsnorm" and cfg.post_block_norm:
        raise ValueError("embedding scaling of sandwich-norm models is not in the reference")
    return cfg


def collaboration(t: Dict[str, Any]):
    from repro.core import Collaboration

    collab = Collaboration()
    for dc in t["datacenters"]:
        collab.add_datacenter(dc, n_dtns=t["dtns_per_dc"])
    return collab


def checkpoint_manager(collab, t: Dict[str, Any], home_dc: str):
    from repro.train import CheckpointManager

    ck = t["ckpt"]
    return CheckpointManager(collab, run=RUN, home_dc=home_dc, mode=ck["mode"], n_shards=ck["shards"])


def trainer(cfg, t: Dict[str, Any], data, *, ckpt_every: int = 0):
    """A one-chip Trainer over ``data``; its own initial state is replaced by :func:`load_state`."""
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model
    from repro.optim import AdamW, AdamWConfig
    from repro.train import Trainer, TrainerConfig

    opt = AdamW(AdamWConfig(**t["optimizer"]))
    mesh = make_mesh((1, 1), ("data", "model"))
    return Trainer(
        Model(cfg), opt, mesh, data,
        TrainerConfig(loss_chunk=min(t["loss_chunk"], t["seq_len"]), ckpt_every=ckpt_every),
        seed=0,
    )


def abstract_state(tr):
    from repro.train import init_state_abstract

    return init_state_abstract(tr.model, tr.optimizer)


def load_state(tr, make_params, key) -> None:
    """Replace the trainer's state by the benchmark's weights and a fresh
    (zero) optimizer state, made on the device in one jitted call."""
    abstract = abstract_state(tr)

    def build(k):
        zeros = lambda a: jnp.zeros(a.shape, a.dtype)
        return {
            "params": make_params(k),
            "opt_state": jax.tree.map(zeros, abstract["opt_state"]),
            "step": zeros(abstract["step"]),
        }

    made = jax.eval_shape(build, key)
    if jax.tree.structure(made) != jax.tree.structure(abstract) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(abstract))
    ):
        raise ValueError("the benchmark's parameter layout differs from the program's")
    tr.state = None  # one copy of the state on the device at a time
    tr.state = jax.jit(build, out_shardings=tr.shardings)(key)
