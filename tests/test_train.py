"""Training substrate: step modes, microbatching, checkpoint/restart, elastic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_variant
from repro.configs.base import ShapeConfig
from repro.core import Collaboration
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.optim import AdamW, AdamWConfig, cosine_schedule, global_norm
from repro.train import CheckpointManager
from repro.train.step import build_train_step, init_state
from tests._multidev import run_multidev

TINY = ShapeConfig("t", "train", 32, 4)


def _setup(arch="codeqwen1.5-7b"):
    cfg = smoke_variant(ARCHS[arch])
    model = Model(cfg)
    opt = AdamW(AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50))
    return cfg, model, opt


def test_microbatch_equivalence():
    """1 microbatch == 4 microbatches (same grads, to fp tolerance)."""
    cfg, model, opt = _setup()
    key = jax.random.PRNGKey(0)
    state1 = init_state(model, opt, key)
    state4 = jax.tree.map(jnp.copy, state1)
    batch = model.make_batch(key, TINY)
    mesh = make_mesh((1,), ("data",))
    s1 = build_train_step(model, opt, mesh, microbatches=1, loss_chunk=16)
    s4 = build_train_step(model, opt, mesh, microbatches=4, loss_chunk=16)
    with jax.set_mesh(mesh):
        state1, m1 = s1(state1, batch)
        state4, m4 = s4(state4, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state1["params"]), jax.tree.leaves(state4["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


def test_train_modes_agree_across_pods():
    out = run_multidev(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import ARCHS, smoke_variant
        from repro.configs.base import ShapeConfig
        from repro.models.model import Model
        from repro.optim import AdamW, AdamWConfig
        from repro.train.step import build_train_step, init_state, state_shardings, shard_state
        from repro.distributed.sharding import batch_shardings
        mesh = make_mesh((2,2,2), ('pod','data','model'))
        cfg = smoke_variant(ARCHS['codeqwen1.5-7b'])
        model = Model(cfg)
        opt = AdamW(AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50))
        tiny = ShapeConfig('t','train',32,8)
        res = {}
        for mode in ('auto','manual','compressed'):
            key = jax.random.PRNGKey(0)
            n_pods = 2 if mode != 'auto' else 0
            state = init_state(model, opt, key, n_pods=n_pods)
            sh = state_shardings(jax.eval_shape(lambda: state), mesh)
            state = shard_state(state, sh)
            step = build_train_step(model, opt, mesh, microbatches=2, loss_chunk=16, cross_pod=mode)
            batch = model.make_batch(key, tiny)
            bs = batch_shardings(jax.eval_shape(lambda: batch), mesh)
            batch = jax.tree.map(jax.device_put, batch, bs)
            with jax.set_mesh(mesh):
                for _ in range(3):
                    state, m = step(state, batch)
            res[mode] = float(m['loss'])
        np.testing.assert_allclose(res['auto'], res['manual'], rtol=1e-5)
        assert abs(res['auto'] - res['compressed']) < 5e-3
        print('modes:', res)
        """,
        devices=8,
        timeout=420,
    )
    assert "modes:" in out


def test_checkpoint_roundtrip_and_discovery(collab):
    cfg, model, opt = _setup("olmoe-1b-7b")
    key = jax.random.PRNGKey(1)
    state = init_state(model, opt, key)
    host = jax.tree.map(np.asarray, state)
    for n_shards in (1, 2, 4):
        mgr = CheckpointManager(
            collab, run=f"rt{n_shards}", home_dc="dc0", n_shards=n_shards
        )
        mgr.save(host, 7)
        mgr.save(host, 12)
        assert mgr.list_steps() == [7, 12]
        out = mgr.restore(jax.eval_shape(lambda: host))
        for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restart_reproduces_uninterrupted_run(collab):
    """Deterministic replay: fail+restore run == never-failed run."""
    from repro.data import ShardedPipeline, SyntheticLM
    from repro.train import FaultInjector, Trainer, TrainerConfig

    cfg, model, opt = _setup()
    pipe = ShardedPipeline(
        SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, period=8), global_batch=4
    )
    mesh = make_mesh((1,), ("data",))

    ckpt = CheckpointManager(collab, run="replay", home_dc="dc0")
    t_fail = Trainer(
        model, opt, mesh, pipe,
        TrainerConfig(loss_chunk=16, ckpt_every=4),
        ckpt=ckpt, fault_hook=FaultInjector(fail_at=[6]),
    )
    r1 = t_fail.run(10)
    assert r1["restarts"] == 1

    t_clean = Trainer(model, opt, mesh, pipe, TrainerConfig(loss_chunk=16))
    r2 = t_clean.run(10)
    assert r1["final_step"] == r2["final_step"] == 10
    np.testing.assert_allclose(r1["final_loss"], r2["final_loss"], rtol=1e-5)


@pytest.mark.parametrize(
    "exc",
    [
        RuntimeError("device fault"),
        jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory"),
        ValueError("bad batch"),
    ],
    ids=lambda e: type(e).__name__,
)
def test_errors_other_than_the_injected_fault_propagate(collab, exc):
    """With a checkpoint to fall back on, only a NodeFailure restores; a
    device or runtime error surfaces at once."""
    from repro.data import ShardedPipeline, SyntheticLM
    from repro.train import Trainer, TrainerConfig

    cfg, model, opt = _setup()
    pipe = ShardedPipeline(
        SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, period=8), global_batch=4
    )

    def hook(step):
        if step == 2:
            raise exc

    trainer = Trainer(
        model, opt, make_mesh((1,), ("data",)), pipe,
        TrainerConfig(loss_chunk=16, ckpt_every=1),
        ckpt=CheckpointManager(collab, run="propagate", home_dc="dc0"), fault_hook=hook,
    )
    with pytest.raises(type(exc)):
        trainer.run(4)
    assert trainer.ckpt.latest_step() == 2
    assert not any("event" in m and m["event"] != "save" for m in trainer.metrics_log)
    assert trainer.current_step() == 2


def test_optimizer_convergence_quadratic():
    opt = AdamW(AdamWConfig(peak_lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0))
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(150):
        grads = jax.tree.map(lambda w: 2 * w, params)  # ∇ of ||w||²
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_cosine_schedule_shape():
    sched = cosine_schedule(1.0, warmup_steps=10, total_steps=100, final_frac=0.1)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert float(sched(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(sched(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)
    assert float(sched(jnp.asarray(55))) < 1.0


def test_grad_clipping():
    from repro.optim import clip_by_global_norm

    tree = {"a": jnp.full((4,), 100.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
