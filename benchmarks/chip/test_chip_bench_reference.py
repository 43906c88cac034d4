"""The plain references against the program, at a small size on the CPU, and
the fp8 control against the real cells' limits."""

import importlib.util

import jax
import jax.numpy as jnp
import pytest

from chipbench import data, harness, program, reference, tiny, training, weights

TRAFFIC = dict(harness.traffic_of("train"), **tiny.SIZES)


def _program_loss_and_grads(c, params, batch):
    from repro.models.model import Model

    cfg = program.model_config(c)  # the tiny configs compute in float32
    model = Model(cfg)
    fn = lambda p: model.train_loss(p, batch, loss_chunk=TRAFFIC["loss_chunk"])[0]
    return jax.jit(jax.value_and_grad(fn))(params)


def _reference_loss_and_grads(c, params, batch):
    fn = lambda p: reference.loss(p, batch["tokens"], batch["targets"], c)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(fn))(params)


@pytest.mark.parametrize(
    "c",
    [tiny.STABLELM, dict(tiny.STABLELM, use_qkv_bias=True), tiny.OLMOE,
     dict(tiny.OLMOE, moe_capacity_factor=0.5)],
    ids=["dense", "dense-qkv-bias", "moe", "moe-dropping"],
)
def test_program_agrees_with_the_reference(c):
    params = jax.jit(lambda k: weights.make_params(c, k))(weights.key_for(2**33 + 7))
    b = data.TokenBatches(3, TRAFFIC, c["vocab_size"]).batch_at(0)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    lp, gp = _program_loss_and_grads(c, params, batch)
    lr, gr = _reference_loss_and_grads(c, params, batch)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = max(float(jnp.abs(b).max()), 1e-6)
        assert float(jnp.abs(a - b).max()) <= 1e-4 * scale


def test_reference_drops_choices_past_capacity():
    """At a capacity below the load, the MoE output changes: choices are dropped."""
    c = tiny.OLMOE
    params = jax.jit(lambda k: weights.make_params(c, k))(weights.key_for(1))
    b = data.TokenBatches(1, TRAFFIC, c["vocab_size"]).batch_at(0)
    full = reference.loss(params, jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]), c)
    tight = reference.loss(params, jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]),
                           dict(c, moe_capacity_factor=0.25))
    assert float(full) != float(tight)


def _control():
    spec = importlib.util.spec_from_file_location("chipbench_control", harness.BENCH / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_control_and_faults_fail_the_cells_limits(cell):
    """control.py's readings at a tiny size, against the real cell's limits:
    the program comes out correct, and the fp8 control, half of the batch and
    an unchanged state each come out not correct."""
    c, traffic, real_cell = tiny.CELLS[cell]
    t = dict(harness.traffic_of(traffic), **tiny.SIZES)
    tr = program.trainer(program.model_config(c), t, None)
    out = _control().readings(tr, c, t, harness.limits_of(real_cell), 2**31 + 5)
    assert out["program"]["correct"], out["program"]
    for way in ("fp8", "half_batch", "unchanged"):
        assert not out[way]["correct"], (way, out[way])


@pytest.mark.parametrize("cell", ["tiny-dense.train_ckpt", "tiny-moe.train"])
def test_fp8_control_separates_from_the_program(cell, tmp_path):
    """The reference computed in fp8, put in the program's place, reads at
    least 3× what the program computing in bfloat16 (as the real
    configurations state) reads, on one of the numbers compared."""
    c, traffic, real_cell = tiny.CELLS[cell]
    program_line = tiny.run(tmp_path, cell, seed=5, compute_dtype="bfloat16")
    lower = {k: v["value"] for k, v in program_line["checks"].items()}
    t = dict(harness.traffic_of(traffic), **tiny.SIZES)
    n = t["reference_steps"]
    ref = training.reference_readings(c, t, 5, n)
    low = training.reference_readings(c, t, 5, n, cast=reference.fp8)
    limits = harness.limits_of(real_cell)
    upper = {ch.name: ch.value for ch in training.compare_training(low, ref, limits, training.leaf_paths(c))}
    assert any(upper[k] >= 3 * lower[k] for k in upper), (lower, upper)
    same = training.compare_training(ref, ref, limits, training.leaf_paths(c))
    assert all(ch.ok and ch.value == 0 for ch in same)
