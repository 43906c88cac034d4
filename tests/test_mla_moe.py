"""Moonlight's mechanisms against plain jnp references on seeded weights, at
a tiny size: multi-head latent attention, flash attention's forward and
backward, sigmoid routing with the correction bias, the leading dense layer,
the bias update beside AdamW, the latent cache, the expert-parallel share,
and a checkpoint round trip of the state."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_variant
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.attention import flash_attention, mla_layer
from repro.models.model import Model
from repro.models.moe import moe_layer
from repro.optim import AdamW, AdamWConfig
from repro.train import CheckpointManager
from repro.train.step import build_train_step, init_state

HI = jax.lax.Precision.HIGHEST


def _cfg(**moe):
    """Tiny Moonlight in float32; capacity high enough that no choice drops."""
    cfg = smoke_variant(ARCHS["moonlight-16b-a3b"])
    return cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0, **moe))


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


# ---------------------------------------------------------------------------
# plain references
# ---------------------------------------------------------------------------


def _rope_source(x, theta):
    """DeepSeek-V3's rotary embedding as its source writes it: de-interleave
    the pairs, then rotate_half with cos/sin over the two halves.  x: [B, S, H, d]."""
    B, S, H, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(S)[:, None] * inv[None]                     # [S, d/2]
    emb = np.concatenate([ang, ang], -1)
    cos, sin = jnp.cos(emb)[None, :, None], jnp.sin(emb)[None, :, None]
    x = x.reshape(B, S, H, d // 2, 2).swapaxes(-1, -2).reshape(B, S, H, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def _ref_mla(p, h, cfg):
    m, H = cfg.mla, cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"]["w"], precision=HI)
    kva = jnp.einsum("bsd,dk->bsk", h, p["wkv_a"]["w"], precision=HI)
    c = _rms(kva[..., : m.kv_lora_rank], p["kv_norm"]["scale"])
    k_pe = kva[..., None, m.kv_lora_rank:]
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"]["w"], precision=HI)
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    q_pe = _rope_source(q[..., m.qk_nope_head_dim:], cfg.rope_theta)
    k_pe = jnp.broadcast_to(_rope_source(k_pe, cfg.rope_theta), (*k_nope.shape[:3], m.qk_rope_head_dim))
    q = jnp.concatenate([q[..., : m.qk_nope_head_dim], q_pe], -1)
    k = jnp.concatenate([k_nope, k_pe], -1)
    S = h.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(m.qk_head_dim)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=HI)
    return jnp.einsum("bshd,hdm->bsm", o, p["wo"]["w"], precision=HI)


def _swiglu(p, h):
    g = jnp.einsum("...d,df->...f", h, p["wi_gate"]["w"], precision=HI)
    u = jnp.einsum("...d,df->...f", h, p["wi_up"]["w"], precision=HI)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["wo"]["w"], precision=HI)


def _ref_moe(p, h, spec, first=0, held=None):
    """Every choice computed (no capacity); only experts [first, first+held)
    add to the output, the shared MLP always.  Returns (out, load, aux)."""
    held = held or spec.n_experts
    s = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", h, p["router"]["w"], precision=HI))
    _, idx = jax.lax.top_k(s + p["router_bias"], spec.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * spec.routed_scale
    out = _swiglu(p["shared"], h) if "shared" in p else jnp.zeros_like(h)
    for e in range(held):
        ep = {"wi_gate": {"w": p["w_gate"]["w"][e]}, "wi_up": {"w": p["w_up"]["w"][e]},
              "wo": {"w": p["w_down"]["w"][e]}}
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = out + weight[..., None] * _swiglu(ep, h)
    onehot = jax.nn.one_hot(idx, spec.n_experts)
    load = onehot.sum((0, 1, 2))
    B, S = h.shape[:2]
    f = onehot.sum((1, 2)) * spec.n_experts / (spec.top_k * S)
    P = (s / s.sum(-1, keepdims=True)).mean(1)
    aux = jnp.mean(jnp.sum(f * P, -1)) * spec.load_balance_coef
    return out, load, aux


def _naive_attention(q, k, v, causal, window, cap):
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, 2), jnp.repeat(v, G, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(q.shape[-1])
    if cap:
        s = cap * jnp.tanh(s / cap)
    qp, kp = jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None]
    ok = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > qp - window
    s = jnp.where(ok, s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=HI)


def _parent_flash_forward(q, k, v, *, causal, window, logit_softcap, chunk_q, chunk_kv):
    """flash_attention's forward as it was before the custom backward: every
    key block scanned for every query block, autodiff through the scan."""
    B, S, H, hd = q.shape
    T_, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    scale = 1.0 / math.sqrt(hd)
    cq, ck = min(chunk_q, S), min(chunk_kv, T_)
    S_real, T_real = S, T_
    if S % cq or T_ % ck:
        S_pad, T_pad = (S + cq - 1) // cq * cq, (T_ + ck - 1) // ck * ck
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, T_pad - T_), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T_), (0, 0), (0, 0)))
        S, T_ = S_pad, T_pad
    nq, nk = S // cq, T_ // ck
    qb = q.reshape(B, nq, cq, Kv, G, hd)
    kb = k.reshape(B, nk, ck, Kv, hd)
    vb = v.reshape(B, nk, ck, Kv, hd)
    q_pos = jnp.arange(S, dtype=jnp.int32).reshape(nq, cq)
    k_pos = jnp.arange(T_, dtype=jnp.int32).reshape(nk, ck)

    def one_q_block(q_chunk, q_positions):
        m0 = jnp.full((B, Kv, G, cq), -1e30, dtype=jnp.float32)
        l0 = jnp.zeros((B, Kv, G, cq), dtype=jnp.float32)
        acc0 = jnp.zeros((B, Kv, G, cq, hd), dtype=jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            k_chunk, v_chunk, k_positions = inp
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_chunk, k_chunk, preferred_element_type=jnp.float32)
            s = s * scale
            if logit_softcap:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            ok = jnp.ones((q_positions.shape[0], k_positions.shape[0]), dtype=bool)
            if causal:
                ok &= k_positions[None, :] <= q_positions[:, None]
            if window:
                ok &= k_positions[None, :] > (q_positions[:, None] - window)
            ok &= (k_positions < T_real)[None, :]
            s = jnp.where(ok[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p, v_chunk, preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, acc0), (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.einsum("bkgqd->bqkgd", out)

    out_blocks = jax.lax.map(lambda a: one_q_block(*a), (jnp.moveaxis(qb, 1, 0), q_pos))
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, S, H, hd)
    return out[:, :S_real].astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = {
    "causal-ragged": dict(S=70, causal=True, window=0, cap=0.0),
    "causal-window": dict(S=64, causal=True, window=24, cap=0.0),
    "bidirectional-softcap": dict(S=50, causal=False, window=0, cap=5.0),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_forward_and_gradients_match_naive(case):
    """v's head size (8) differs from q/k's (12); GQA 4 heads over 2."""
    c = FLASH_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (2, c["S"], 4, 12))
    k = jax.random.normal(ks[1], (2, c["S"], 2, 12))
    v = jax.random.normal(ks[2], (2, c["S"], 2, 8))
    flash = lambda q, k, v: flash_attention(q, k, v, causal=c["causal"], window=c["window"],
                                            logit_softcap=c["cap"], chunk_q=16, chunk_kv=8)
    naive = lambda q, k, v: _naive_attention(q, k, v, c["causal"], c["window"], c["cap"])
    np.testing.assert_allclose(flash(q, k, v), naive(q, k, v), atol=2e-6)
    w = jax.random.normal(ks[3], (2, c["S"], 4, 8))
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(lambda *a: jnp.sum(naive(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_forward_is_bit_identical_to_the_full_scan(case, dtype):
    """Skipping the blocks a mask hides changes no bit of the forward."""
    c = FLASH_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q, k, v = (jax.random.normal(kk, (2, c["S"], 4, 16)).astype(dtype) for kk in ks)
    kw = dict(causal=c["causal"], window=c["window"], logit_softcap=c["cap"], chunk_q=16, chunk_kv=32)
    new = jax.jit(lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v)
    old = jax.jit(lambda q, k, v: _parent_flash_forward(q, k, v, **kw))(q, k, v)
    assert jnp.array_equal(new, old)


def test_flash_attention_backward_keeps_no_per_block_residual():
    """What the forward saves for the backward grows with S·d, not with the
    number of block pairs: at 4× the length, ≤ 4× the residual bytes."""

    def residual_bytes(S):
        q = jnp.zeros((1, S, 2, 8))
        fn = lambda q: flash_attention(q, q, q, chunk_q=8, chunk_kv=8)
        _, vjp = jax.vjp(fn, q)
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(vjp))

    assert residual_bytes(256) <= 4 * residual_bytes(64)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def test_mla_layer_matches_the_source_equations():
    cfg = _cfg()
    params = Model(cfg).init(jax.random.PRNGKey(3))
    p = params["lead"]["layer0"]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    S = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    out, _ = mla_layer(p, h, positions, cfg.replace(attn_chunk_q=8, attn_chunk_kv=8), dtype=jnp.float32)
    np.testing.assert_allclose(out, _ref_mla(p, h, cfg), atol=2e-5, rtol=2e-5)


def test_sigmoid_routing_with_bias_scale_and_shared_experts():
    cfg = _cfg()
    assert cfg.moe.n_shared == 2 and cfg.moe.scoring == "sigmoid"
    params = Model(cfg).init(jax.random.PRNGKey(5))
    p = jax.tree.map(lambda a: a[0], params["units"]["pos0"]["ffn"])
    p["router_bias"] = jnp.asarray([0.3, -0.2, 0.0, 0.1])  # moves the choices
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, aux, stats = moe_layer(p, h, cfg, dtype=jnp.float32)
    ref, load, ref_aux = _ref_moe(p, h, cfg.moe)
    assert p["shared"]["wi_gate"]["w"].shape == (cfg.d_model, 2 * cfg.moe.d_ff)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(stats["load"], load)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-6)
    assert int(stats["routed"]) == int(stats["kept"]) == 2 * 16 * cfg.moe.top_k


def test_leading_dense_layer_runs_once_before_the_units():
    cfg = _cfg()
    params = Model(cfg).init(jax.random.PRNGKey(7))
    assert set(params["lead"]) == {"layer0"}
    lead = params["lead"]["layer0"]
    assert lead["ffn"]["wi_gate"]["w"].shape == (cfg.d_model, cfg.d_ff)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, cfg.vocab_size)
    x = params["embed"]["table"][tokens]
    x = x + _ref_mla(lead["mixer"], _rms(x, lead["norm1"]["scale"]), cfg)
    x = x + _swiglu(lead["ffn"], _rms(x, lead["norm2"]["scale"]))
    unit = jax.tree.map(lambda a: a[0], params["units"]["pos0"])
    x = x + _ref_mla(unit["mixer"], _rms(x, unit["norm1"]["scale"]), cfg)
    x = x + _ref_moe(unit["ffn"], _rms(x, unit["norm2"]["scale"]), cfg.moe)[0]
    x = _rms(x, params["final_norm"]["scale"])
    with jax.default_matmul_precision("highest"):
        hidden, _ = T.lm_hidden(params, {"tokens": tokens}, cfg)
    np.testing.assert_allclose(hidden, x, atol=5e-5, rtol=5e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 chips' shares of 16 experts (2 each): their outputs, the shared MLP
    counted once, add up to the whole layer's output."""
    cfg = _cfg(n_experts=16, top_k=4)
    params = Model(cfg).init(jax.random.PRNGKey(9))
    whole = jax.tree.map(lambda a: a[0], params["units"]["pos0"]["ffn"])
    whole["router_bias"] = jax.random.normal(jax.random.PRNGKey(10), (16,)) * 0.1
    h = jax.random.normal(jax.random.PRNGKey(13), (2, 16, cfg.d_model))
    full, _, full_stats = moe_layer(whole, h, cfg, dtype=jnp.float32)
    total = jnp.zeros_like(full)
    routed = 0
    for chip in range(8):
        share = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=2, first_held=2 * chip))
        p = dict(whole, **{n: {"w": whole[n]["w"][2 * chip: 2 * chip + 2]}
                           for n in ("w_gate", "w_up", "w_down")})
        if chip:
            p.pop("shared")  # every chip computes the shared MLP alike: count it once
            share = share.replace(moe=dataclasses.replace(share.moe, n_shared=0))
        out, _, stats = moe_layer(p, h, share, dtype=jnp.float32)
        total = total + out
        routed += int(stats["routed"])
        np.testing.assert_array_equal(stats["load"], full_stats["load"])
    np.testing.assert_allclose(total, full, atol=1e-5, rtol=1e-5)
    assert routed == int(full_stats["routed"]) == 2 * 16 * 4
    ref = _ref_moe(whole, h, cfg.moe)[0]
    np.testing.assert_allclose(full, ref, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# training: AdamW beside the bias update, and the checkpoint
# ---------------------------------------------------------------------------

TINY = ShapeConfig("t", "train", 16, 2)


def _plain_adamw(params, mu, nu, grads, count, c: AdamWConfig, frozen):
    """One AdamW step on already clipped gradients; frozen leaves untouched."""
    lr = AdamW(c).schedule(jnp.asarray(count))

    def one(p, m, v, g, still):
        if still:
            return p, m, v
        m = c.b1 * m + (1 - c.b1) * g
        v = c.b2 * v + (1 - c.b2) * g * g
        u = (m / (1 - c.b1 ** count)) / (jnp.sqrt(v / (1 - c.b2 ** count)) + c.eps)
        return p - lr * (u + c.weight_decay * p), m, v

    out = jax.tree.map(one, params, mu, nu, grads, frozen)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def test_three_adamw_steps_with_the_bias_update():
    """Each of 3 steps of the train step against a plain AdamW step that skips
    the bias, and the bias moved by γ·sign(mean load − load).  The plain step
    takes the gradient the program used (read back from its first moment, so
    a near-zero gradient's sign is the same on both sides); that gradient is
    checked against a separate differentiation, leaf by leaf in norm."""
    cfg = _cfg()
    model = Model(cfg)
    oc = AdamWConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10)
    opt = AdamW(oc)
    state = init_state(model, opt, jax.random.PRNGKey(14))
    frozen = model.state_leaves(state["params"])
    assert sum(jax.tree.leaves(frozen)) == 1  # the one MoE layer's bias
    mesh = make_mesh((1,), ("data",))
    step = build_train_step(model, opt, mesh, loss_chunk=8, donate=False)
    bias = lambda p: p["units"]["pos0"]["ffn"]["router_bias"]
    loss_fn = lambda p, b: model.train_loss(p, b, loss_chunk=8)
    for i in range(3):
        batch = model.make_batch(jax.random.PRNGKey(20 + i), TINY)
        before = state
        with jax.set_mesh(mesh):
            state, metrics = step(state, batch)
        (_, m), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(before["params"], batch)
        assert float(jnp.abs(bias(grads)).max()) == 0.0
        used = jax.tree.map(lambda new, old: (new - oc.b1 * old) / (1 - oc.b1),
                            state["opt_state"]["mu"], before["opt_state"]["mu"])
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clipped = jax.tree.map(lambda g: g * jnp.minimum(1.0, oc.clip_norm / gnorm), grads)
        for a, b in zip(jax.tree.leaves(used), jax.tree.leaves(clipped)):
            assert float(jnp.linalg.norm(a - b)) <= 1e-4 * max(float(jnp.linalg.norm(b)), 1e-6)
        params, mu, nu = _plain_adamw(before["params"], before["opt_state"]["mu"],
                                      before["opt_state"]["nu"], used, i + 1, oc, frozen)
        load = m["moe"]["load"]["units/pos0/ffn/router_bias"].astype(jnp.float32)
        params["units"]["pos0"]["ffn"]["router_bias"] = (
            bias(before["params"]) + cfg.moe.bias_rate * jnp.sign(load.mean(-1, keepdims=True) - load))
        for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(params)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(state["opt_state"]["nu"]), jax.tree.leaves(nu)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-12)
        np.testing.assert_array_equal(bias(state["params"]), bias(params))
        assert int(metrics["moe"]["routed"]) == 2 * 16 * cfg.moe.top_k
    assert float(jnp.abs(bias(state["params"])).max()) > 0
    assert float(jnp.abs(bias(state["opt_state"]["mu"])).max()) == 0


def test_prefill_and_decode_through_the_latent_cache_match_the_forward():
    cfg = _cfg()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(15))
    params["units"]["pos0"]["ffn"]["router_bias"] = jnp.asarray([[0.2, -0.1, 0.0, 0.05]])
    cache = model.init_decode_cache(1, 32)
    m = cfg.mla
    assert cache["lead"]["layer0"]["c"].shape == (1, 1, 32, m.kv_lora_rank)
    assert cache["pos0"]["k_pe"].shape == (1, 1, 32, m.qk_rope_head_dim)
    S = 12
    tokens = jax.random.randint(jax.random.PRNGKey(16), (1, S + 3), 0, cfg.vocab_size)

    def forward_logits(n):
        hidden, _ = T.lm_hidden(params, {"tokens": tokens[:, :n]}, cfg)
        return T._logits(params, hidden[:, -1:], cfg)

    cache, logits = model.prefill(params, {"tokens": tokens[:, :S]}, max_len=32)
    np.testing.assert_allclose(logits, forward_logits(S), atol=1e-4, rtol=1e-4)
    for t in range(S, S + 3):
        cache, logits = model.decode_step(params, cache, tokens[:, t: t + 1], jnp.int32(t))
        np.testing.assert_allclose(logits, forward_logits(t + 1), atol=1e-4, rtol=1e-4)


def test_state_with_the_bias_survives_a_native_checkpoint(collab):
    cfg = _cfg()
    model = Model(cfg)
    opt = AdamW(AdamWConfig())
    state = init_state(model, opt, jax.random.PRNGKey(17))
    state["params"]["units"]["pos0"]["ffn"]["router_bias"] = jnp.asarray([[0.003, -0.001, 0.0, 0.002]])
    host = jax.tree.map(np.asarray, state)
    mgr = CheckpointManager(collab, run="moonlight", home_dc="dc0", mode="native", n_shards=2)
    mgr.save(host, 3)
    other = CheckpointManager(collab, run="moonlight", home_dc="dc1", mode="native", n_shards=2)
    out = other.restore(jax.eval_shape(lambda: host), other.latest_step())
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(out["params"]["units"]["pos0"]["ffn"]["router_bias"],
                                  host["params"]["units"]["pos0"]["ffn"]["router_bias"])


def test_bias_and_counters_agree_across_pods():
    """Manual cross-pod mode sums the MoE loads and counters over pods before
    the bias moves, so it ends where the one-program (auto) mode does."""
    from tests._multidev import run_multidev

    out = run_multidev(
        """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import ARCHS, smoke_variant
        from repro.configs.base import ShapeConfig
        from repro.models.model import Model
        from repro.optim import AdamW, AdamWConfig
        from repro.train.step import build_train_step, init_state, state_shardings, shard_state
        from repro.distributed.sharding import batch_shardings
        mesh = make_mesh((2, 2, 1), ('pod', 'data', 'model'))
        cfg = smoke_variant(ARCHS['moonlight-16b-a3b'])
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        model = Model(cfg)
        opt = AdamW(AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50))
        batch0 = model.make_batch(jax.random.PRNGKey(1), ShapeConfig('t', 'train', 16, 8))
        res = {}
        for mode in ('auto', 'manual'):
            n_pods = 2 if mode != 'auto' else 0
            state = init_state(model, opt, jax.random.PRNGKey(0), n_pods=n_pods)
            state = shard_state(state, state_shardings(jax.eval_shape(lambda: state), mesh))
            step = build_train_step(model, opt, mesh, microbatches=2, loss_chunk=8, cross_pod=mode)
            batch = jax.tree.map(jax.device_put, batch0, batch_shardings(jax.eval_shape(lambda: batch0), mesh))
            with jax.set_mesh(mesh):
                for _ in range(2):
                    state, m = step(state, batch)
            res[mode] = (float(m['loss']), np.asarray(state['params']['units']['pos0']['ffn']['router_bias']),
                         {k: int(v) for k, v in m['moe'].items()})
        np.testing.assert_allclose(res['auto'][0], res['manual'][0], rtol=1e-5)
        np.testing.assert_array_equal(res['auto'][1], res['manual'][1])
        assert res['auto'][2] == res['manual'][2] and res['auto'][2]['routed'] == 8 * 16 * cfg.moe.top_k
        print('agree:', res['auto'][2])
        """,
        devices=4,
        timeout=420,
    )
    assert "agree:" in out
