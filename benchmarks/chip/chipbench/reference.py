"""Plain float32 reference of the decoder LM a configuration file describes.

Straight ``jax.numpy`` at ``highest`` matmul precision: no kernels, no
caches, no one-hot dispatch.  It imports nothing of the program under test;
it only reads the same parameter layout (``embed``, ``units/pos0/...``,
``final_norm``, ``lm_head``), as a checkpoint converter would.

It follows the published architecture with the departures the program makes
(each noted in ``PERF.md``): every norm, QK-norm included, uses the file's
one eps (1e-6 as the program runs), RoPE rotates
interleaved pairs of the first ``partial_rotary_factor`` of each head, the
QK-norm of OLMoE is per head, and the MoE layer keeps the program's stated
capacity (``moe_capacity_factor`` slots per expert and row, choices queued
k-major then by position; a choice past capacity adds nothing) and its
top-k renormalisation.  The load-balance loss is Switch-style on the top-1
assignment, times ``router_aux_loss_coef``.

Memory: rows go through the stack one at a time (``lax.map``) with every
layer and the loss head rematerialised, so a 4 × 2048 step of a 3 B model
fits one chip beside its f32 AdamW state.

``cast`` puts a lower precision in the reference's place: it is applied to
both operands of every matmul.  :func:`fp8` is the control for a
configuration that computes in bfloat16.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# lower-precision control
# ---------------------------------------------------------------------------


def _quantize(x, dtype):
    """Per-tensor scaled round trip through ``dtype`` (float8)."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(x):
    """fp8 training recipe: e4m3 forward operands, e5m2 backward cotangents."""
    return _quantize(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_quantize(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(spec: str, a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _eps(c) -> float:
    return c["layer_norm_eps"] if c["norm"] == "layernorm" else c["rms_norm_eps"]


def _norm(p, x, kind: str, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, c):
    """x: [S, H, hd]; interleaved pairs of the first rotary dims."""
    hd = x.shape[-1]
    rot = int(hd * c["partial_rotary_factor"]) // 2 * 2
    if rot == 0:
        return x
    inv = 1.0 / (c["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(*x.shape[:-1], rot)
    return jnp.concatenate([out, x[..., rot:]], -1)


def _attention(p, h, c, cast):
    """Causal self-attention over one row.  h: [S, D]."""
    S = h.shape[0]

    def proj(name):
        out = _mm("sd,dhk->shk", h, p[name]["w"], cast)
        return out + p[name]["b"] if "b" in p[name] else out

    q, k, v = proj("wq"), proj("wk"), proj("wv")
    if c.get("qk_norm"):
        q, k = _norm(p["q_norm"], q, "rmsnorm", _eps(c)), _norm(p["k_norm"], k, "rmsnorm", _eps(c))
    q, k = _rope(q, c), _rope(k, c)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = _mm("qhd,khd->hqk", q, k, cast) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, cast)
    return _mm("qhd,hdm->qm", o, p["wo"]["w"], cast)


def _dense_ffn(p, h, cast):
    g = _mm("sd,df->sf", h, p["wi_gate"]["w"], cast)
    u = _mm("sd,df->sf", h, p["wi_up"]["w"], cast)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, p["wo"]["w"], cast)


def _moe_ffn(p, h, c, cast):
    """Top-k routed experts over one row.  Returns (out, top-1 counts, prob sums)."""
    S, D = h.shape
    E, K = c["num_experts"], c["num_experts_per_tok"]
    C = max(1, int(math.ceil(S * K * c["moe_capacity_factor"] / E)))
    probs = jax.nn.softmax(_mm("sd,de->se", h, p["router"]["w"], cast), -1)
    gates, idx = jax.lax.top_k(probs, K)
    if c.get("norm_topk_prob", True):
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # queue position of every (choice, token) at its expert, k-major
    onehot = jax.nn.one_hot(idx.T.reshape(K * S), E, dtype=jnp.int32)  # [K*S, E]
    pos = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)       # [K*S]
    expert = idx.T.reshape(K * S)
    slot = jnp.where(pos < C, expert * C + pos, E * C)                 # E*C: dropped
    token = jnp.tile(jnp.arange(S), K)
    tok_of_slot = jnp.full((E * C,), S, jnp.int32).at[slot].set(token, mode="drop")
    gate_of_slot = jnp.zeros((E * C,), jnp.float32).at[slot].set(gates.T.reshape(K * S), mode="drop")
    xe = jnp.concatenate([h, jnp.zeros((1, D), h.dtype)])[tok_of_slot].reshape(E, C, D)
    g = _mm("ecd,edf->ecf", xe, p["w_gate"]["w"], cast)
    u = _mm("ecd,edf->ecf", xe, p["w_up"]["w"], cast)
    y = _mm("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"]["w"], cast)
    y = y.reshape(E * C, D) * gate_of_slot[:, None]
    out = jnp.zeros((S + 1, D), jnp.float32).at[tok_of_slot].add(y)[:S]
    top1 = jnp.sum(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), 0)
    return out, top1, probs.sum(0)


def _layer(lp, x, c, cast):
    kind, eps = c["norm"], _eps(c)
    x = x + _attention(lp["mixer"], _norm(lp["norm1"], x, kind, eps), c, cast)
    h = _norm(lp["norm2"], x, kind, eps)
    if c.get("num_experts"):
        f, top1, psum = _moe_ffn(lp["ffn"], h, c, cast)
    else:
        f, top1, psum = _dense_ffn(lp["ffn"], h, cast), jnp.zeros((1,)), jnp.zeros((1,))
    return x + f, top1, psum


def _row(params, tokens, targets, c, cast):
    """One row through the stack: (summed NLL, per-layer top-1 counts, prob sums)."""
    x = params["embed"]["table"][tokens]
    units = params["units"]["pos0"]
    layer = jax.checkpoint(lambda lp, x: _layer(lp, x, c, cast))
    stats = []
    for i in range(c["num_hidden_layers"]):
        x, top1, psum = layer(jax.tree.map(lambda a: a[i], units), x)
        stats.append((top1, psum))
    x = _norm(params["final_norm"], x, c["norm"], _eps(c))
    logits = _mm("sd,vd->sv", x, params["lm_head"]["table"], cast)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    top1 = jnp.stack([s[0] for s in stats])
    psum = jnp.stack([s[1] for s in stats])
    return nll.sum(), top1, psum


def loss(params, tokens, targets, c: Dict[str, Any], cast=None):
    """Mean next-token NLL over the batch plus the load-balance loss."""
    B, S = tokens.shape
    row = jax.checkpoint(lambda t, y: _row(params, t, y, c, cast))
    nll, top1, psum = jax.lax.map(lambda ty: row(*ty), (tokens, targets))
    total = nll.sum() / (B * S)
    if c.get("num_experts"):
        frac = top1.sum(0) / (B * S)   # [L, E]
        mean_prob = psum.sum(0) / (B * S)
        total = total + c["num_experts"] * jnp.sum(frac * mean_prob) * c["router_aux_loss_coef"]
    return total


# ---------------------------------------------------------------------------
# AdamW, as the job configures it
# ---------------------------------------------------------------------------


def lr_at(count, opt: Dict[str, Any]):
    """Linear warm-up then cosine decay to ``final_frac`` of the peak."""
    step = count.astype(jnp.float32)
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    t = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(step < warm, peak * jnp.minimum(1.0, step / max(warm, 1)), peak * cos)


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)])


def make_step(c: Dict[str, Any], opt: Dict[str, Any], cast=None):
    """Jitted reference train step on (params, mu, nu, count, tokens, targets).

    Returns the new (params, mu, nu, count), the loss, the global gradient
    norm before clipping, and the per-leaf norms of the clipped gradient.
    """

    def step(params, mu, nu, count, tokens, targets):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(loss)(params, tokens, targets, c, cast)
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

        params = jax.tree.map(upd, params, mu, nu)
        return params, mu, nu, count, value, gnorm, leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0, 1, 2))
