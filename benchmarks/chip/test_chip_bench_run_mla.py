"""The train_mla job at a tiny size on the CPU, with the look for a chip
skipped: the plain reference agrees with the program, a whole run comes out
correct against the real cell's limits, and the fp8 control, half of the
batch and an unchanged state do not; the FLOPs against a hand count; the
weights' layout against the program's; the ``moe.slot_fill`` reader."""

import argparse
import importlib.util
import json
import math
import shutil
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import data, faults, flops_mla, harness, program, program_mla, reference_mla, weights, weights_mla

CELL = "moonlight-16b-a3b.train_8k"
TINY_CELL = "tiny-mla.train_8k"

#: the real configuration's keys at a size a CPU test can hold
TINY = dict(
    harness.load_json(harness.BENCH / "configs" / "moonlight-16b-a3b.json"),
    name="tiny-mla", hidden_size=64, intermediate_size=128, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=4, num_key_value_heads=4,
    moe_intermediate_size=32, n_routed_experts=4, ep_size=2, num_experts_per_tok=3,
    num_hidden_layers=3, vocab_size=512, compute_dtype="float32",
)
SIZES = {"batch": 4, "seq_len": 64, "loss_chunk": 32, "vocab_eff": 256}
TRAFFIC = dict(harness.traffic_of("train_8k"), **SIZES)


def _tree(root, **config_changes):
    """A benchmark tree under ``root`` with the tiny cell, taking the real cell's limits."""
    bench = root / "benchmarks" / "chip"
    for d in ("metrics", "jobs"):
        shutil.copytree(harness.BENCH / d, bench / d, ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    (bench / "configs" / "tiny-mla.json").write_text(json.dumps(dict(TINY, **config_changes)))
    (bench / "traffic" / "tiny-train_8k.json").write_text(json.dumps(TRAFFIC))
    (bench / "limits" / f"{TINY_CELL}.json").write_text(json.dumps(harness.limits_of(CELL)))
    real = harness.load_spec()
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY_CELL if w == CELL else w for w in m["workloads"]]
    return {"configs": [{"name": "tiny-mla", "file": "benchmarks/chip/configs/tiny-mla.json"}],
            "workloads": [{"name": TINY_CELL, "config": "tiny-mla", "traffic": "tiny-train_8k", "chips": 1}],
            "end_to_end": real["end_to_end"], "per_layer": real["per_layer"]}


def _run(root, *, trace=0, seed=2**31 + 17, **config_changes):
    spec = _tree(root, **config_changes)
    args = argparse.Namespace(workload=TINY_CELL, seed=seed, seconds=0.3, trace=trace)
    return harness.run_cell(args, t_start=time.perf_counter(), spec=spec, root=root,
                            bench=root / "benchmarks" / "chip",
                            chip_check=lambda chips: jax.devices(), compile_cache=False)


def test_reference_agrees_with_the_program():
    from repro.models.model import Model

    params = jax.jit(lambda k: weights_mla.make_params(TINY, k))(weights.key_for(2**33 + 3))
    params["units"]["pos0"]["ffn"]["router_bias"] = jnp.tile(jnp.linspace(-0.05, 0.05, 8), (2, 1))
    b = data.TokenBatches(3, TRAFFIC, TINY["vocab_size"]).batch_at(0)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    model = Model(program_mla.model_config(TINY))
    fn = lambda p: model.train_loss(p, batch, loss_chunk=TRAFFIC["loss_chunk"])
    (lp, metrics), gp = jax.jit(jax.value_and_grad(fn, has_aux=True))(params)
    ref = lambda p: reference_mla.loss(p, batch["tokens"], batch["targets"], TINY)
    with jax.default_matmul_precision("highest"):
        (lr, counts), gr = jax.jit(jax.value_and_grad(ref, has_aux=True))(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = max(float(jnp.abs(b).max()), 1e-6)
        assert float(jnp.abs(a - b).max()) <= 1e-4 * scale
    assert jnp.array_equal(metrics["moe"]["load"]["units/pos0/ffn/router_bias"], counts)


def test_sound_run_is_correct(tmp_path):
    line = _run(tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {"loss", "grad_norm", "grad_leaf", "update_leaf", "bias_update"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_caught(tmp_path, monkeypatch, fault):
    faults.FAULTS[fault](monkeypatch)
    line = _run(tmp_path)
    assert not line["correct"], line["checks"]


def _control():
    spec = importlib.util.spec_from_file_location("chipbench_control_mla", harness.BENCH / "control_mla.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_control_and_faults_fail_the_cells_limits():
    """control_mla.py's readings at a tiny size against the real cell's
    limits: the program comes out correct; the fp8 control, half of the batch
    and an unchanged state each come out not correct."""
    tr = program.trainer(program_mla.model_config(TINY), TRAFFIC, None)
    out = _control().readings(tr, TINY, TRAFFIC, harness.limits_of(CELL), 2**31 + 5)
    assert out["program"]["correct"], out["program"]
    for way in ("fp8", "half_batch", "unchanged"):
        assert not out[way]["correct"], (way, out[way])


def test_flops_by_hand():
    """Per token and layer: MLA projections q 2·2048·16·192, latent
    2·2048·576, keys/values 2·512·16·256, output 2·16·128·2048, and the
    causal scores and values 2·16·(192+128)·(8193/2); layer 0's SwiGLU of
    11264; each MoE layer's router 2·2048·64, 6·8/64 of an expert of 1408
    and the shared 2816; the head 2·2048·20480.  ×3, ×32768 tokens."""
    c = harness.config_of(harness.load_spec(), "moonlight-16b-a3b")
    mla = (2 * 2048 * 16 * 192 + 2 * 2048 * 576 + 2 * 512 * 16 * 256 + 2 * 16 * 128 * 2048
           + 2 * 16 * 320 * 8193 / 2)
    moe = 2 * 2048 * 64 + 6 * 8 / 64 * 6 * 2048 * 1408 + 6 * 2048 * 2816
    per_token = 5 * mla + 6 * 2048 * 11264 + 4 * moe + 2 * 2048 * 20480
    assert flops_mla.train_step(c, 4, 8192) == pytest.approx(3 * per_token * 32768)
    assert flops_mla.train_step(c, 4, 8192) == pytest.approx(74.8e12, rel=0.001)


def test_weights_layout_is_the_programs_parameter_tree():
    from repro.models.model import Model

    for c in (TINY, harness.config_of(harness.load_spec(), "moonlight-16b-a3b")):
        ours = weights_mla.abstract_params(c)
        theirs = Model(program_mla.model_config(c)).init_abstract()
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        assert all((a.shape, a.dtype) == (b.shape, b.dtype)
                   for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    full = weights_mla.abstract_params(harness.config_of(harness.load_spec(), "moonlight-16b-a3b"))
    assert sum(x.size for x in jax.tree.leaves(full)) == pytest.approx(568.5e6, rel=1e-3)


def test_slot_fill_reads_the_step_tags(tmp_path, monkeypatch):
    """A tiny run tags every step with the MoE counters; the reader sums kept
    over slots.  Without the tags (a program that has none) it reads nothing."""
    from repro.train import TELEMETRY

    from chipbench import program_spans

    _run(tmp_path)
    steps = [s for s in TELEMETRY.spans.spans() if s.name == "train.step"][-3:]
    c = program_mla.model_config(TINY)
    C = math.ceil(SIZES["seq_len"] * c.moe.top_k * c.moe.capacity_factor / c.moe.n_experts)
    for s in steps:
        assert s.tags["moe_slots"] == 2 * SIZES["batch"] * c.moe.held * C  # 2 MoE layers
        assert 0 < s.tags["moe_kept"] <= s.tags["moe_routed"] <= 2 * SIZES["batch"] * SIZES["seq_len"] * 3
    reader = harness.metric_reader("moe.slot_fill")
    monkeypatch.setattr(program_spans, "window", lambda ctx: steps)
    want = 100 * sum(s.tags["moe_kept"] for s in steps) / sum(s.tags["moe_slots"] for s in steps)
    assert reader(None) == pytest.approx(want) and 0 < want <= 100

    class Untagged:  # a step span of a program that keeps no MoE counters
        name, tags = "train.step", None

    monkeypatch.setattr(program_spans, "window", lambda ctx: [Untagged(), Untagged()])
    assert reader(None) is None
    monkeypatch.setattr(program_spans, "window", lambda ctx: None)
    assert reader(None) is None
