"""Decoder-only LM over heterogeneous layer patterns (all non-encdec archs).

The stack is ``cfg.n_units`` repeats of ``cfg.pattern`` (a tuple of
LayerSpecs).  Parameters for each pattern position are stacked across units
so the whole depth compiles as ONE ``lax.scan`` over units — the HLO is
unit-sized regardless of depth (Jamba's 8-layer unit, Gemma2's 2-layer
local/global unit, plain archs' 1-layer unit).  Activation rematerialization
wraps the scanned unit body (``cfg.remat``).

Leading layers (``cfg.lead``, e.g. DeepSeek-V3's first dense layer) run
once before the scan, each under ``lead/layer<i>``.

Three entry points per model: ``train_loss`` (causal LM loss, sequence-
chunked so [B,S,V] logits never materialize), ``prefill`` (forward + cache
build), ``decode_step`` (one token against caches).  Caches are stacked per
pattern position, mirroring the parameter layout, so decode also scans.

An MoE layer with a correction bias (``MoESpec.bias_rate``) keeps it as the
parameter ``ffn/router_bias``, which the optimizer leaves alone
(:func:`state_leaves`); after each step :func:`update_router_bias` moves it
by the step's expert loads, which ``train_loss`` returns.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (
    attention_layer,
    decode_attention_layer,
    decode_mla_layer,
    init_attention,
    init_kv_cache,
    init_mla,
    init_mla_cache,
    mla_layer,
)
from .layers import (
    Params,
    dense,
    embed,
    init_dense,
    init_embedding,
    init_mlp,
    init_norm,
    mlp,
    norm,
    softcap,
    unembed,
)
from .mamba import init_mamba, init_mamba_cache, mamba_decode_step, mamba_layer
from .moe import init_moe, moe_layer
from .rwkv6 import (
    init_rwkv_cache,
    init_rwkv_cmix,
    init_rwkv_tmix,
    rwkv_cmix,
    rwkv_tmix,
)

__all__ = [
    "init_lm",
    "lm_hidden",
    "train_loss",
    "prefill",
    "decode_step",
    "init_decode_cache",
    "count_params",
    "count_active_params",
    "state_leaves",
    "update_router_bias",
]


def _dtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg, spec) -> Params:
    pdt = _pdtype(cfg)
    keys = jax.random.split(key, 4)
    p: Params = {"norm1": init_norm(cfg.norm, cfg.d_model, param_dtype=pdt)}
    if spec.mixer in ("attn", "attn_local"):
        p["mixer"] = init_attention(keys[0], cfg, param_dtype=pdt)
    elif spec.mixer == "mla":
        p["mixer"] = init_mla(keys[0], cfg, param_dtype=pdt)
    elif spec.mixer == "mamba":
        p["mixer"] = init_mamba(keys[0], cfg, param_dtype=pdt)
    elif spec.mixer == "rwkv":
        p["mixer"] = init_rwkv_tmix(keys[0], cfg, param_dtype=pdt)
    p["norm2"] = init_norm(cfg.norm, cfg.d_model, param_dtype=pdt)
    if spec.ffn == "dense":
        p["ffn"] = init_mlp(keys[1], cfg.d_model, cfg.d_ff, activation=cfg.activation, param_dtype=pdt)
    elif spec.ffn == "moe":
        p["ffn"] = init_moe(keys[1], cfg, param_dtype=pdt)
    elif spec.ffn == "rwkv_cmix":
        p["ffn"] = init_rwkv_cmix(keys[1], cfg, param_dtype=pdt)
    if cfg.post_block_norm:
        p["norm1_post"] = init_norm(cfg.norm, cfg.d_model, param_dtype=pdt)
        p["norm2_post"] = init_norm(cfg.norm, cfg.d_model, param_dtype=pdt)
    return p


#: folded into the init key for the leading layers' keys
_LEAD_KEY = 0x1EAD


def init_lm(key, cfg) -> Params:
    pdt = _pdtype(cfg)
    k_embed, k_units, k_head, k_front = jax.random.split(key, 4)
    params: Params = {"embed": init_embedding(k_embed, cfg.vocab_size, cfg.d_model, param_dtype=pdt)}
    params["final_norm"] = init_norm(cfg.norm, cfg.d_model, param_dtype=pdt)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(k_head, cfg.vocab_size, cfg.d_model, param_dtype=pdt)
    if cfg.frontend == "vision":
        params["frontend_proj"] = init_dense(k_front, cfg.frontend_dim, (cfg.d_model,), param_dtype=pdt)

    # stacked unit params: vmap the per-layer init over unit keys
    unit_keys = jax.random.split(k_units, cfg.n_units)
    units: Params = {}
    for i, spec in enumerate(cfg.pattern):
        pos_keys = jax.vmap(lambda k, i=i: jax.random.fold_in(k, i))(unit_keys)
        units[f"pos{i}"] = jax.vmap(lambda k, s=spec: _init_layer(k, cfg, s))(pos_keys)
    params["units"] = units
    if cfg.lead:
        k_lead = jax.random.fold_in(key, _LEAD_KEY)
        params["lead"] = {
            f"layer{i}": _init_layer(jax.random.fold_in(k_lead, i), cfg, spec)
            for i, spec in enumerate(cfg.lead)
        }
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch: Dict[str, jax.Array], cfg) -> jax.Array:
    dt = _dtype(cfg)
    x = embed(params["embed"], batch["tokens"], dtype=dt)
    if cfg.norm == "rmsnorm" and cfg.post_block_norm:
        # Gemma-style embedding scaling
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=dt)
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        pe = dense(params["frontend_proj"], batch["patch_embeds"], dtype=dt)
        n = pe.shape[1]
        x = jnp.concatenate([pe, x[:, n:, :]], axis=1)
    return x


def _mixer_apply(lp, spec, h, positions, cfg, dt):
    if spec.mixer in ("attn", "attn_local"):
        out, _ = attention_layer(lp["mixer"], h, positions, cfg, kind=spec.mixer, dtype=dt)
        return out
    if spec.mixer == "mla":
        out, _ = mla_layer(lp["mixer"], h, positions, cfg, dtype=dt)
        return out
    if spec.mixer == "mamba":
        return mamba_layer(lp["mixer"], h, cfg, dtype=dt)
    if spec.mixer == "rwkv":
        out, _ = rwkv_tmix(lp["mixer"], h, cfg, dtype=dt)
        return out
    raise ValueError(spec.mixer)


def _ffn_apply(lp, spec, h, cfg, dt):
    """Returns (out, aux, MoE stats or None)."""
    if spec.ffn == "dense":
        return mlp(lp["ffn"], h, activation=cfg.activation, dtype=dt), 0.0, None
    if spec.ffn == "moe":
        return moe_layer(lp["ffn"], h, cfg, dtype=dt)
    if spec.ffn == "rwkv_cmix":
        out, _ = rwkv_cmix(lp["ffn"], h, cfg, dtype=dt)
        return out, 0.0, None
    raise ValueError(spec.ffn)


#: the MoE counters summed over layers: choices bound for held experts,
#: choices kept within capacity, expert slots computed
_MOE_COUNTERS = ("routed", "kept", "slots")


def _zero_counters():
    from repro.distributed.vma import vary

    return vary({name: jnp.zeros((), jnp.int32) for name in _MOE_COUNTERS})


def _layer_train(lp, spec, x, positions, cfg, dt):
    """One block: (x, aux, MoE stats or None)."""
    h = norm(lp["norm1"], x, kind=cfg.norm)
    mix = _mixer_apply(lp, spec, h, positions, cfg, dt)
    if cfg.post_block_norm:
        mix = norm(lp["norm1_post"], mix, kind=cfg.norm)
    x = x + mix
    h = norm(lp["norm2"], x, kind=cfg.norm)
    f, aux, stats = _ffn_apply(lp, spec, h, cfg, dt)
    if cfg.post_block_norm:
        f = norm(lp["norm2_post"], f, kind=cfg.norm)
    return x + f, aux, stats


def _add_counters(counters, stats):
    if stats is None:
        return counters
    return {name: counters[name] + stats[name] for name in _MOE_COUNTERS}


def _unit_body_train(cfg):
    dt = _dtype(cfg)

    def body(carry, unit_params, positions):
        """Returns the new carry and the expert loads of the unit's biased MoE layers."""
        x, aux, counters = carry
        loads = {}
        for i, spec in enumerate(cfg.pattern):
            x, aux_i, stats = _layer_train(unit_params[f"pos{i}"], spec, x, positions, cfg, dt)
            aux = aux + aux_i
            counters = _add_counters(counters, stats)
            if stats is not None and "router_bias" in unit_params[f"pos{i}"]["ffn"]:
                loads[f"pos{i}"] = stats["load"]
        return (x, aux, counters), loads

    return body


_REMAT_POLICIES = {
    "unit": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _remat(fn, cfg):
    if cfg.remat in _REMAT_POLICIES:
        return jax.checkpoint(fn, policy=_REMAT_POLICIES[cfg.remat], prevent_cse=False)
    return fn


def _hidden(params, batch: Dict[str, jax.Array], cfg):
    """Embeddings → leading layers → stacked units → final norm.

    Returns (hidden, moe_aux, moe) with ``moe`` None for a model without
    MoE layers, else the counters summed over layers and ``load``: the
    expert loads of each biased layer, keyed by its bias's parameter path."""
    x = _embed_inputs(params, batch, cfg)
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    dt = _dtype(cfg)
    from repro.distributed.vma import vary

    aux, counters = vary(jnp.zeros((), jnp.float32)), _zero_counters()
    loads: Dict[str, jax.Array] = {}
    for i, spec in enumerate(cfg.lead):
        lp = params["lead"][f"layer{i}"]
        layer = _remat(lambda lp, x, spec=spec: _layer_train(lp, spec, x, positions, cfg, dt), cfg)
        x, aux_i, stats = layer(lp, x)
        aux = aux + aux_i
        counters = _add_counters(counters, stats)
        if stats is not None and "router_bias" in lp["ffn"]:
            loads[f"lead/layer{i}/ffn/router_bias"] = stats["load"]

    body = _unit_body_train(cfg)
    scan_fn = _remat(lambda carry, unit_params: body(carry, unit_params, positions), cfg)
    carry = (x, aux, counters)
    if cfg.scan_layers:
        carry, unit_loads = jax.lax.scan(scan_fn, carry, params["units"])
    else:
        per_unit = []
        for u in range(cfg.n_units):
            unit = jax.tree.map(lambda leaf: leaf[u], params["units"])
            carry, ul = scan_fn(carry, unit)
            per_unit.append(ul)
        unit_loads = jax.tree.map(lambda *ls: jnp.stack(ls), *per_unit)
    x, aux, counters = carry
    for pos, load in unit_loads.items():
        loads[f"units/{pos}/ffn/router_bias"] = load
    x = norm(params["final_norm"], x, kind=cfg.norm)
    has_moe = any(s.ffn == "moe" for s in cfg.lead + cfg.pattern)
    return x, aux, (dict(counters, load=loads) if has_moe else None)


def lm_hidden(params, batch: Dict[str, jax.Array], cfg) -> Tuple[jax.Array, jax.Array]:
    """Embeddings → leading layers → stacked units → final norm.  Returns (hidden, moe_aux)."""
    x, aux, _ = _hidden(params, batch, cfg)
    return x, aux


def _logits(params, x: jax.Array, cfg) -> jax.Array:
    dt = _dtype(cfg)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(head, x, dtype=dt)
    return softcap(logits, cfg.final_softcap)


def train_loss(
    params,
    batch: Dict[str, jax.Array],
    cfg,
    *,
    loss_chunk: int = 256,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal LM cross-entropy, sequence-chunked so [B,S,V] never exists.

    The metrics hold ``moe`` (see :func:`_hidden`) for a model with MoE layers."""
    x, aux, moe = _hidden(params, batch, cfg)
    targets = batch["targets"]
    B, S = targets.shape
    c = min(loss_chunk, S)
    assert S % c == 0
    n = S // c
    xc = x.reshape(B, n, c, -1)
    tc = targets.reshape(B, n, c)

    def chunk_loss(carry, inp):
        xx, tt = inp  # [B, c, D], [B, c]
        logits = _logits(params, xx, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction instead of take_along_axis: the gather over
        # a vocab-sharded logits tensor forces an all-gather of the full
        # [B, c, V] block per chunk; the contraction stays vocab-local
        # and reduces with a tiny [B, c] psum.
        onehot = jax.nn.one_hot(tt, logits.shape[-1], dtype=logits.dtype)
        picked = jnp.sum(logits * onehot, axis=-1)
        nll = lse - picked
        return carry + nll.sum(), None

    if getattr(cfg, "remat_loss_chunk", False):
        # recompute the [B, c, V] logits in the backward pass instead of
        # saving one residual per chunk
        chunk_loss = jax.checkpoint(chunk_loss, prevent_cse=False)

    from repro.distributed.vma import vary

    total, _ = jax.lax.scan(
        chunk_loss, vary(jnp.zeros((), jnp.float32)), (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(tc, 1, 0))
    )
    loss = total / (B * S) + aux
    metrics = {"loss": loss, "moe_aux": aux}
    if moe is not None:
        metrics["moe"] = moe
    return loss, metrics


def state_leaves(params):
    """True at each leaf that is model state moved outside the optimizer:
    the MoE correction biases."""
    from repro.distributed.sharding import path_of

    return jax.tree_util.tree_map_with_path(
        lambda kp, _: path_of(kp).endswith("router_bias"), params
    )


def update_router_bias(params, loads: Dict[str, jax.Array], cfg):
    """b_i += rate·sign(mean_j c_j − c_i) for each biased MoE layer, with c
    the step's choices per expert (DeepSeek-V3, arXiv:2412.19437 §2.1.2)."""
    from repro.distributed.sharding import path_of

    rate = cfg.moe.bias_rate

    def update(kp, leaf):
        load = loads.get(path_of(kp))
        if load is None:
            return leaf
        load = load.astype(jnp.float32)
        return leaf + rate * jnp.sign(load.mean(axis=-1, keepdims=True) - load)

    return jax.tree_util.tree_map_with_path(update, params)


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------


def _cache_entry(cfg, spec, batch: int, max_len: int, n: int) -> Dict[str, Any]:
    """The cache of one layer kind, stacked over ``n`` layers."""
    dt = _dtype(cfg)
    if spec.mixer in ("attn", "attn_local"):
        # local layers never need more than the window
        T = max_len
        if spec.mixer == "attn_local" and cfg.attn_window:
            T = min(max_len, cfg.attn_window)
        kv = init_kv_cache(cfg, batch, T, n_layers_of_kind=n, dtype=dt)
        entry: Dict[str, Any] = {"k": kv["k"], "v": kv["v"]}
    elif spec.mixer == "mla":
        entry = init_mla_cache(cfg, batch, max_len, n_layers_of_kind=n, dtype=dt)
    elif spec.mixer == "mamba":
        mc = init_mamba_cache(cfg, batch, n_layers_of_kind=n, dtype=dt)
        entry = {"conv": mc["conv"], "ssm": mc["ssm"]}
    elif spec.mixer == "rwkv":
        rc = init_rwkv_cache(cfg, batch, n_layers_of_kind=n, dtype=dt)
        entry = {"wkv": rc["wkv"], "tshift": rc["tshift"]}
    else:
        raise ValueError(spec.mixer)
    if spec.ffn == "rwkv_cmix":
        entry["cshift"] = jnp.zeros((n, batch, 1, cfg.d_model), dtype=dt)
    return entry


def init_decode_cache(cfg, batch: int, max_len: int) -> Dict:
    """Stacked-per-position cache pytree matching the scan layout; a leading
    layer's cache is under ``lead/layer<i>`` with a leading dim of 1, so every
    leaf is [layers, batch, ...]."""
    cache: Dict[str, Any] = {
        f"pos{i}": _cache_entry(cfg, spec, batch, max_len, cfg.n_units)
        for i, spec in enumerate(cfg.pattern)
    }
    if cfg.lead:
        cache["lead"] = {
            f"layer{i}": _cache_entry(cfg, spec, batch, max_len, 1) for i, spec in enumerate(cfg.lead)
        }
    return cache


def _layer_decode(lp, lc, spec, x, pos, cfg, dt):
    """One block, one token against its cache: (x, new cache)."""
    nc: Dict[str, Any] = {}
    h = norm(lp["norm1"], x, kind=cfg.norm)
    if spec.mixer in ("attn", "attn_local"):
        # local windows use a ring cache sized to the window: the
        # cache was allocated at min(max_len, window), so it rolls
        # exactly when it was clamped to the window size
        T = lc["k"].shape[1]
        rolling = spec.mixer == "attn_local" and bool(cfg.attn_window) and T == cfg.attn_window
        write_pos = pos % T if rolling else pos
        mix, ck, cv = decode_attention_layer(
            lp["mixer"], h, lc["k"], lc["v"], write_pos, cfg,
            kind=spec.mixer, dtype=dt, rolling=rolling, abs_pos=pos,
        )
        nc["k"], nc["v"] = ck, cv
    elif spec.mixer == "mla":
        mix, nc["c"], nc["k_pe"] = decode_mla_layer(lp["mixer"], h, lc["c"], lc["k_pe"], pos, cfg, dtype=dt)
    elif spec.mixer == "mamba":
        mix, conv, ssm = mamba_decode_step(lp["mixer"], h, lc["conv"], lc["ssm"], cfg, dtype=dt)
        nc["conv"], nc["ssm"] = conv, ssm
    elif spec.mixer == "rwkv":
        mix, st = rwkv_tmix(lp["mixer"], h, cfg, dtype=dt, state={"wkv": lc["wkv"], "shift": lc["tshift"]})
        nc["wkv"], nc["tshift"] = st["wkv"], st["shift"]
    if cfg.post_block_norm:
        mix = norm(lp["norm1_post"], mix, kind=cfg.norm)
    x = x + mix
    h = norm(lp["norm2"], x, kind=cfg.norm)
    if spec.ffn == "rwkv_cmix":
        f, st = rwkv_cmix(lp["ffn"], h, cfg, dtype=dt, state={"shift": lc["cshift"]})
        nc["cshift"] = st["shift"]
    else:
        f = _ffn_apply(lp, spec, h, cfg, dt)[0]
    if cfg.post_block_norm:
        f = norm(lp["norm2_post"], f, kind=cfg.norm)
    return x + f, nc


def _layer_prefill(lp, lc, spec, x, positions, cfg, dt):
    """One block over the prompt, filling its cache: (x, new cache)."""
    S = x.shape[1]
    nc: Dict[str, Any] = {}
    h = norm(lp["norm1"], x, kind=cfg.norm)
    if spec.mixer in ("attn", "attn_local"):
        mix, kv = attention_layer(
            lp["mixer"], h, positions, cfg, kind=spec.mixer, dtype=dt, return_kv=True
        )
        k_new, v_new = kv
        T = lc["k"].shape[1]
        if T >= S:
            nc["k"] = jax.lax.dynamic_update_slice_in_dim(lc["k"], k_new.astype(lc["k"].dtype), 0, axis=1)
            nc["v"] = jax.lax.dynamic_update_slice_in_dim(lc["v"], v_new.astype(lc["v"].dtype), 0, axis=1)
        else:  # rolling window cache keeps the tail
            nc["k"] = k_new[:, S - T :].astype(lc["k"].dtype)
            nc["v"] = v_new[:, S - T :].astype(lc["v"].dtype)
    elif spec.mixer == "mla":
        mix, (c, k_pe) = mla_layer(lp["mixer"], h, positions, cfg, dtype=dt, return_cache=True)
        for name, new in (("c", c), ("k_pe", k_pe)):
            nc[name] = jax.lax.dynamic_update_slice_in_dim(lc[name], new.astype(lc[name].dtype), 0, axis=1)
    elif spec.mixer == "mamba":
        # rerun the mixer capturing final states
        from .mamba import mamba_layer_with_state

        mix, conv, ssm = mamba_layer_with_state(lp["mixer"], h, cfg, dtype=dt)
        nc["conv"], nc["ssm"] = conv.astype(lc["conv"].dtype), ssm
    elif spec.mixer == "rwkv":
        mix, st = rwkv_tmix(
            lp["mixer"], h, cfg, dtype=dt,
            state={"wkv": lc["wkv"], "shift": lc["tshift"]},
        )
        nc["wkv"], nc["tshift"] = st["wkv"], st["shift"].astype(lc["tshift"].dtype)
    if cfg.post_block_norm:
        mix = norm(lp["norm1_post"], mix, kind=cfg.norm)
    x = x + mix
    h = norm(lp["norm2"], x, kind=cfg.norm)
    if spec.ffn == "rwkv_cmix":
        f, st = rwkv_cmix(lp["ffn"], h, cfg, dtype=dt, state={"shift": lc["cshift"]})
        nc["cshift"] = st["shift"].astype(lc["cshift"].dtype)
    else:
        f = _ffn_apply(lp, spec, h, cfg, dt)[0]
    if cfg.post_block_norm:
        f = norm(lp["norm2_post"], f, kind=cfg.norm)
    return x + f, nc


def _through_layers(layer_fn, params, cache, x, cfg):
    """The leading layers, then the scanned units, each as ``layer_fn(lp, lc,
    spec, x) → (x, new cache)``; returns (x, new cache)."""
    new_cache: Dict[str, Any] = {}
    if cfg.lead:
        new_cache["lead"] = {}
        for i, spec in enumerate(cfg.lead):
            name = f"layer{i}"
            lc = jax.tree.map(lambda leaf: leaf[0], cache["lead"][name])
            x, nc = layer_fn(params["lead"][name], lc, spec, x)
            new_cache["lead"][name] = jax.tree.map(lambda leaf: leaf[None], nc)
    units_cache = {k: v for k, v in cache.items() if k != "lead"}

    def body(x, inp):
        unit_params, unit_cache = inp
        unit_new: Dict[str, Any] = {}
        for i, spec in enumerate(cfg.pattern):
            x, unit_new[f"pos{i}"] = layer_fn(unit_params[f"pos{i}"], unit_cache[f"pos{i}"], spec, x)
        return x, unit_new

    if cfg.scan_layers:
        x, units_new = jax.lax.scan(body, x, (params["units"], units_cache))
    else:
        slices = []
        for u in range(cfg.n_units):
            unit_p = jax.tree.map(lambda l: l[u], params["units"])
            unit_c = jax.tree.map(lambda l: l[u], units_cache)
            x, nc = body(x, (unit_p, unit_c))
            slices.append(nc)
        units_new = jax.tree.map(lambda *ls: jnp.stack(ls), *slices)
    new_cache.update(units_new)
    return x, new_cache


def decode_step(params, cache: Dict, token: jax.Array, pos: jax.Array, cfg):
    """One decode step.  token: [B, 1] int32; pos: scalar or [B] int32
    (per-slot positions — continuous batching).

    Returns (new_cache, logits [B, 1, V]).
    """
    dt = _dtype(cfg)
    x = embed(params["embed"], token, dtype=dt)
    if cfg.post_block_norm:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=dt)
    layer = lambda lp, lc, spec, x: _layer_decode(lp, lc, spec, x, pos, cfg, dt)
    x, new_cache = _through_layers(layer, params, cache, x, cfg)
    x = norm(params["final_norm"], x, kind=cfg.norm)
    logits = _logits(params, x, cfg)
    return new_cache, logits


def prefill(params, batch: Dict[str, jax.Array], cfg, *, max_len: int):
    """Forward over a prompt, building decode caches.

    Implemented as hidden-pass + per-position cache fill; attention caches
    are populated from the layer K/V projections (MLA's from its latent and
    rotary key), recurrent caches from the chunked-scan final states.
    Returns (cache, last_logits [B,1,V]).
    """
    dt = _dtype(cfg)
    x = _embed_inputs(params, batch, cfg)
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cache = init_decode_cache(cfg, B, max_len)
    layer = lambda lp, lc, spec, x: _layer_prefill(lp, lc, spec, x, positions, cfg, dt)
    x, new_cache = _through_layers(layer, params, cache, x, cfg)
    x = norm(params["final_norm"], x, kind=cfg.norm)
    last_logits = _logits(params, x[:, -1:, :], cfg)
    return new_cache, last_logits


# ---------------------------------------------------------------------------
# parameter counting (roofline MODEL_FLOPS inputs)
# ---------------------------------------------------------------------------


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def count_active_params(params, cfg) -> int:
    """Active params per token: MoE expert stacks count top_k/E of their size."""
    total = count_params(params)
    if cfg.moe is None:
        return total
    inactive = 0
    units = params["units"]
    for i, spec in enumerate(cfg.pattern):
        if spec.ffn != "moe":
            continue
        for name in ("w_gate", "w_up", "w_down"):
            leaf = units[f"pos{i}"]["ffn"][name]["w"]
            frac_inactive = 1.0 - (cfg.moe.top_k / cfg.moe.n_experts)
            inactive += int(leaf.size * frac_inactive)
    return total - inactive
