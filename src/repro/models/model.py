"""Model facade: one uniform API over the decoder-only and enc-dec families.

Every architecture in :mod:`repro.configs` is driven through this interface
by the trainer, the serving engine, and the dry-run:

    model = Model(cfg)
    params = model.init(key)
    loss, metrics = model.train_loss(params, batch)
    cache, logits = model.prefill(params, batch, max_len=...)
    cache, logits = model.decode_step(params, cache, token, pos)

``input_shapes(shape)`` describes the batch pytree for a given input-shape
cell — the single source of truth shared by the data pipeline (which
materializes real arrays) and ``launch.dryrun`` (which turns the same dict
into ShapeDtypeStructs, never allocating).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, transformer

__all__ = ["Model"]


class Model:
    """Family dispatch: 'encdec' → :mod:`.encdec`; everything else → :mod:`.transformer`."""

    def __init__(self, cfg):
        self.cfg = cfg
        self._mod = encdec if cfg.is_encdec else transformer

    # -- construction -----------------------------------------------------------
    def init(self, key: jax.Array):
        if self.cfg.is_encdec:
            return encdec.init_encdec(key, self.cfg)
        return transformer.init_lm(key, self.cfg)

    def init_abstract(self):
        """Parameter pytree as ShapeDtypeStructs (dry-run: no allocation)."""
        return jax.eval_shape(self.init, jax.ShapeDtypeStruct((2,), jnp.uint32))

    # -- steps -------------------------------------------------------------------
    def train_loss(self, params, batch, *, loss_chunk: int = 256):
        return self._mod.train_loss(params, batch, self.cfg, loss_chunk=loss_chunk)

    def prefill(self, params, batch, *, max_len: int):
        return self._mod.prefill(params, batch, self.cfg, max_len=max_len)

    def decode_step(self, params, cache, token, pos):
        return self._mod.decode_step(params, cache, token, pos, self.cfg)

    # -- state moved outside the optimizer -------------------------------------------
    def state_leaves(self, params):
        """Pytree of bools: True at leaves the optimizer must leave alone
        (the MoE correction biases), moved by :meth:`after_step` instead."""
        if self.cfg.is_encdec:
            return jax.tree.map(lambda _: False, params)
        return transformer.state_leaves(params)

    def after_step(self, params, metrics):
        """Params after the step's own state updates: each MoE correction bias
        moves by the expert loads in the step's ``metrics["moe"]["load"]``."""
        moe = metrics.get("moe")
        if not moe or not moe["load"]:
            return params
        return transformer.update_router_bias(params, moe["load"], self.cfg)

    def init_decode_cache(self, batch: int, max_len: int):
        if self.cfg.is_encdec:
            return encdec.init_decode_cache(
                self.cfg, batch, max_len, encdec.enc_len_for(self.cfg, max_len)
            )
        return transformer.init_decode_cache(self.cfg, batch, max_len)

    # -- shape metadata ------------------------------------------------------------
    def input_shapes(self, shape) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """{name: (shape, dtype)} for one input-shape cell (train or prefill).

        ``shape`` is a :class:`repro.configs.base.ShapeConfig`; decode cells
        describe the per-step token input — the KV cache is separate state
        (see :meth:`init_decode_cache`).
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        dt = jnp.dtype(cfg.dtype)
        out: Dict[str, Tuple[Tuple[int, ...], Any]] = {}
        if shape.kind == "train":
            out["tokens"] = ((B, S), jnp.int32)
            out["targets"] = ((B, S), jnp.int32)
        elif shape.kind == "prefill":
            out["tokens"] = ((B, S), jnp.int32)
        else:  # decode: one new token
            out["tokens"] = ((B, 1), jnp.int32)
        if cfg.is_encdec and shape.kind in ("train", "prefill"):
            out["frames"] = ((B, encdec.enc_len_for(cfg, S), cfg.frontend_dim), dt)
        if cfg.frontend == "vision" and shape.kind in ("train", "prefill"):
            out["patch_embeds"] = ((B, cfg.frontend_tokens, cfg.frontend_dim), dt)
        return out

    def make_batch(self, key: jax.Array, shape) -> Dict[str, jax.Array]:
        """Materialize a synthetic batch matching :meth:`input_shapes`."""
        out: Dict[str, jax.Array] = {}
        for name, (shp, dtype) in self.input_shapes(shape).items():
            key, sub = jax.random.split(key)
            if jnp.issubdtype(dtype, jnp.integer):
                out[name] = jax.random.randint(sub, shp, 0, self.cfg.vocab_size, dtype=dtype)
            else:
                out[name] = jax.random.normal(sub, shp, dtype=dtype)
        return out

    # -- accounting ----------------------------------------------------------------
    def count_params(self, params) -> int:
        return transformer.count_params(params)

    def count_active_params(self, params) -> int:
        if self.cfg.is_encdec:
            return transformer.count_params(params)
        return transformer.count_active_params(params, self.cfg)
