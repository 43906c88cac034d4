"""Plain float32 reference of a DeepSeek-V3-style configuration file.

Straight ``jax.numpy`` at ``highest`` matmul precision, no kernels, no
caches, no one-hot dispatch, and nothing of the program under test: it
reads the parameter layout of :mod:`chipbench.weights_mla`, as a checkpoint
converter would.  Per layer, with h the normed input (DeepSeek-V3,
arXiv:2412.19437 §2.1, and its published modelling code):

- MLA, no query compression: q = h·W_q split into q_nope and q_pe; the
  latent [c, k_pe] = h·W_kva with c RMS-normed; [k_nope, v] = c·W_kvb per
  head; RoPE on q_pe and k_pe, k_pe shared by every head; causal softmax at
  scale 1/√(nope + rope); o·W_o.  Each row's attention runs in query blocks
  of at most 1024 rows, so a step fits one chip beside its f32 AdamW state.
- The leading layers: a dense SwiGLU.  The rest: sigmoid scores s over all
  experts, the top k of s + b chosen (b the correction bias, for choosing
  only), weights s over their sum × ``routed_scaling_factor``; of the
  choices only those bound for the experts held here are computed (the
  chip's share), plus the shared SwiGLU of n_shared × the expert width.
- DeepSeek-V3's sequence-wise balance loss, α Σ_i f_i P_i per row, averaged.
- After each AdamW step, which leaves b alone, b_i += γ·sign(mean load − load_i)
  with the step's choices per expert (§2.1.2).

Departures of the program the reference follows (``assumed`` in the
configuration file): every norm's eps is the file's one (1e-6 as the program
runs), RoPE rotates interleaved pairs (the source de-interleaves before
``rotate_half``, which gives the same scores), and each held expert keeps
``moe_capacity_factor`` slots per row, choices queued k-major then by
position, a choice past capacity adding nothing (the source is dropless).

``cast`` puts a lower precision in place of float32 on both operands of
every matmul, as :mod:`chipbench.reference` does.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .reference import _dense_ffn, _mm, _norm, _rope, leaf_norms, lr_at

#: the largest query block of a row's attention
QUERY_BLOCK = 1024


def _rotate(x, c):
    """RoPE over every dim of x [S, H, d], interleaved pairs."""
    return _rope(x, {"partial_rotary_factor": 1.0, "rope_theta": c["rope_theta"]})


def _mla(p, h, c, cast):
    """Causal MLA over one row.  h: [S, D]."""
    S = h.shape[0]
    R, nope, rope = c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    q = _mm("sd,dhk->shk", h, p["wq"]["w"], cast)
    kva = _mm("sd,dk->sk", h, p["wkv_a"]["w"], cast)
    latent = _norm(p["kv_norm"], kva[:, :R], "rmsnorm", c["rms_norm_eps"])
    kv = _mm("sr,rhk->shk", latent, p["wkv_b"]["w"], cast)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = _rotate(kva[:, None, R:], c)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], c)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:2], rope))], -1)
    blk = min(QUERY_BLOCK, S)
    assert S % blk == 0, (S, blk)

    @jax.checkpoint
    def block(args):
        q_blk, first = args
        s = _mm("qhd,khd->hqk", q_blk, k, cast) / math.sqrt(nope + rope)
        causal = jnp.arange(S)[None, :] <= (first + jnp.arange(blk))[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, cast)

    o = jax.lax.map(block, (q.reshape(S // blk, blk, *q.shape[1:]), jnp.arange(0, S, blk)))
    return _mm("qhd,hdm->qm", o.reshape(S, *o.shape[2:]), p["wo"]["w"], cast)


def _moe(p, h, c, cast):
    """The held experts' part of the routed experts plus the shared MLP over
    one row.  Returns (out [S, D], the row's balance loss before α, choices per expert [E])."""
    S, D = h.shape
    E, K = c["n_routed_experts"] * c["ep_size"], c["num_experts_per_tok"]
    held, first = c["n_routed_experts"], c["first_held_expert"]
    C = max(1, int(math.ceil(S * K * c["moe_capacity_factor"] / E)))
    s = jax.nn.sigmoid(_mm("sd,de->se", h, p["router"]["w"], cast))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["router_bias"]), K)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9) * c["routed_scaling_factor"]
    # queue position of every (choice, token) bound here, k-major
    local = idx.T.reshape(K * S) - first
    onehot = jax.nn.one_hot(local, held, dtype=jnp.int32)          # zero rows: elsewhere
    pos = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)
    here = (local >= 0) & (local < held) & (pos < C)
    slot = jnp.where(here, local * C + pos, held * C)                # held*C: not computed here
    token = jnp.tile(jnp.arange(S), K)
    tok_of_slot = jnp.full((held * C,), S, jnp.int32).at[slot].set(token, mode="drop")
    w_of_slot = jnp.zeros((held * C,), jnp.float32).at[slot].set(w.T.reshape(K * S), mode="drop")
    xe = jnp.concatenate([h, jnp.zeros((1, D), h.dtype)])[tok_of_slot].reshape(held, C, D)
    g = _mm("ecd,edf->ecf", xe, p["w_gate"]["w"], cast)
    u = _mm("ecd,edf->ecf", xe, p["w_up"]["w"], cast)
    y = _mm("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"]["w"], cast)
    y = y.reshape(held * C, D) * w_of_slot[:, None]
    out = jnp.zeros((S + 1, D), jnp.float32).at[tok_of_slot].add(y)[:S]
    out = out + _dense_ffn(p["shared"], h, cast)
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), (0, 1))
    f = counts * E / (K * S)
    P = jnp.mean(s / s.sum(-1, keepdims=True), 0)
    return out, jnp.sum(f * P), counts


def _layer(lp, x, c, cast, moe: bool):
    eps = c["rms_norm_eps"]
    x = x + _mla(lp["mixer"], _norm(lp["norm1"], x, "rmsnorm", eps), c, cast)
    h = _norm(lp["norm2"], x, "rmsnorm", eps)
    if moe:
        f, aux, counts = _moe(lp["ffn"], h, c, cast)
    else:
        f, aux, counts = _dense_ffn(lp["ffn"], h, cast), jnp.zeros(()), None
    return x + f, aux, counts


def _row(params, tokens, targets, c, cast):
    """One row through the stack: (summed NLL, summed balance loss, choices per layer and expert)."""
    x = params["embed"]["table"][tokens]
    for i in range(c["first_k_dense_replace"]):
        layer = jax.checkpoint(lambda lp, x: _layer(lp, x, c, cast, False)[:2])
        x, _ = layer(params["lead"][f"layer{i}"], x)
    units = params["units"]["pos0"]
    layer = jax.checkpoint(lambda lp, x: _layer(lp, x, c, cast, True))
    aux, counts = jnp.zeros(()), []
    for i in range(c["num_hidden_layers"] - c["first_k_dense_replace"]):
        x, aux_i, counts_i = layer(jax.tree.map(lambda a: a[i], units), x)
        aux, counts = aux + aux_i, counts + [counts_i]
    x = _norm(params["final_norm"], x, "rmsnorm", c["rms_norm_eps"])
    logits = _mm("sd,vd->sv", x, params["lm_head"]["table"], cast)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return nll.sum(), aux, jnp.stack(counts)


def loss(params, tokens, targets, c: Dict[str, Any], cast=None):
    """(mean next-token NLL plus α × the mean row balance loss, choices per MoE layer and expert)."""
    B, S = tokens.shape
    row = jax.checkpoint(lambda t, y: _row(params, t, y, c, cast))
    nll, aux, counts = jax.lax.map(lambda ty: row(*ty), (tokens, targets))
    return nll.sum() / (B * S) + c["aux_loss_alpha"] * aux.mean(), counts.sum(0)


def make_step(c: Dict[str, Any], opt: Dict[str, Any], cast=None):
    """Jitted reference train step on (params, mu, nu, count, tokens, targets).

    AdamW, as the job configures it, moves every leaf but the correction
    bias, which then moves by γ·sign(mean load − load).  Returns the new
    (params, mu, nu, count), the loss, the global gradient norm before
    clipping, and the per-leaf norms of the clipped gradient."""

    def step(params, mu, nu, count, tokens, targets):
        with jax.default_matmul_precision("highest"):
            (value, counts), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, targets, c, cast)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        count = count + 1
        lr = lr_at(count, opt)
        b1, b2 = opt["b1"], opt["b2"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def upd(p, m, v):
            u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p
            return p - lr * u

        bias = params["units"]["pos0"]["ffn"]["router_bias"]
        params = jax.tree.map(upd, params, mu, nu)
        moved = bias + c["bias_update_speed"] * jnp.sign(counts.mean(-1, keepdims=True) - counts)
        params["units"]["pos0"]["ffn"]["router_bias"] = moved
        return params, mu, nu, count, value, gnorm, leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0, 1, 2))
