"""Tiny stand-ins of the cells, for the benchmark's own tests on the CPU.

:func:`tree` writes a benchmark tree into a scratch directory: the real
metric readers and peaks, and configuration, traffic and limits files of the
same shape as the real ones at sizes a test run can hold.  The limits are the
real cells' limits.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Dict

from . import harness

STABLELM = {
    "name": "tiny-dense", "source": "test", "program_arch": "stablelm-3b",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 512, "hidden_act": "silu",
    "partial_rotary_factor": 0.25, "rope_theta": 10000, "norm": "layernorm", "layer_norm_eps": 1e-6,
    "use_qkv_bias": False, "qk_norm": False, "tie_word_embeddings": False,
    "compute_dtype": "float32", "param_dtype": "float32", "reduced": [],
}

OLMOE = {
    "name": "tiny-moe", "source": "test", "program_arch": "olmoe-1b-7b",
    "hidden_size": 64, "num_hidden_layers": 1, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 32, "vocab_size": 512, "hidden_act": "silu",
    "partial_rotary_factor": 1.0, "rope_theta": 10000, "norm": "rmsnorm", "rms_norm_eps": 1e-6,
    "use_qkv_bias": False, "qk_norm": "per_head", "tie_word_embeddings": False,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.01, "moe_capacity_factor": 1.25,
    "compute_dtype": "float32", "param_dtype": "float32", "reduced": [],
}

SIZES = {"batch": 4, "seq_len": 64, "loss_chunk": 32, "vocab_eff": 256}

#: tiny cell → (config, real traffic it shrinks, real cell whose limits it takes)
CELLS = {
    "tiny-dense.train_ckpt": (STABLELM, "train_ckpt", "stablelm-3b.train_ckpt"),
    "tiny-moe.train": (OLMOE, "train", "olmoe-1b-7b.train"),
    "tiny-dense.resume_xdc": (STABLELM, "resume_xdc", "stablelm-3b.resume_xdc"),
}


def tree(root: Path, **config_changes) -> Dict[str, Any]:
    """Write the tiny benchmark under ``root``, with ``config_changes`` made
    to every configuration; returns its BENCHMARK spec."""
    bench = root / "benchmarks" / "chip"
    for d in ("metrics", "jobs"):
        shutil.copytree(harness.BENCH / d, bench / d, ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    real = harness.load_spec()
    spec: Dict[str, Any] = {"configs": [], "workloads": [], "end_to_end": real["end_to_end"],
                            "per_layer": []}
    for cell, (config, traffic, real_cell) in CELLS.items():
        cfile = bench / "configs" / f"{config['name']}.json"
        cfile.write_text(json.dumps(dict(config, **config_changes)))
        if config["name"] not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append({"name": config["name"], "file": str(cfile.relative_to(root))})
        t = dict(harness.traffic_of(traffic), **SIZES)
        name = f"tiny-{traffic}"
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(harness.limits_of(real_cell)))
        spec["workloads"].append({"name": cell, "config": config["name"], "traffic": name, "chips": 1})
    renamed = {real_cell: cell for cell, (_, _, real_cell) in CELLS.items()}
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [renamed.get(w, w) for w in m["workloads"]]
    spec["per_layer"] = real["per_layer"]
    return spec


def run(root: Path, cell: str, *, seed: int = 2**31 + 11, seconds: float = 0.3,
        **config_changes) -> Dict[str, Any]:
    """One run of a tiny cell with the look for a chip skipped; its result line."""
    import argparse
    import time

    import jax

    spec = tree(root, **config_changes)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=0)
    return harness.run_cell(args, t_start=time.perf_counter(), spec=spec, root=root,
                            bench=root / "benchmarks" / "chip",
                            chip_check=lambda chips: jax.devices(), compile_cache=False)
