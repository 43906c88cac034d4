"""The harness: it finds every piece by name, new pieces are new files, and
it prints no result without a chip."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files():
    spec = harness.load_spec()
    assert spec["paths"] == ["benchmarks/chip"]
    for c in spec["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        body = harness.load_json(ROOT / c["file"])
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and "assumed" in body
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        harness.config_of(spec, w["config"])
        assert callable(harness.job_runner(harness.traffic_of(w["traffic"])["job"]))
        assert harness.limits_of(w["name"])
        for trace in (0, 1):
            assert harness.metrics_for(spec, w["name"], bool(trace))
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in spec["end_to_end"])


def test_per_layer_metrics_follow_their_cells():
    spec = harness.load_spec()
    names = lambda cell: {m["name"] for m in harness.metrics_for(spec, cell, True)}
    assert "ckpt_save.stall_s" in names("stablelm-3b.train_ckpt")
    assert "ckpt_save.stall_s" not in names("olmoe-1b-7b.train")
    assert names("stablelm-3b.resume_xdc") == {"setup.compile_s", "ws_meta.discover_s", "ckpt_restore.s"}
    e2e = lambda cell: {m["name"] for m in harness.metrics_for(spec, cell, False)}
    assert e2e("olmoe-1b-7b.train") == {"train_tokens_per_s", "setup_s"}


def _digest_tree(root: Path):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in root.rglob("*") if p.is_file()}


def test_a_new_config_traffic_and_metric_are_new_files_only(tmp_path):
    """Add a configuration, a mix and a metric as new files plus entries in
    BENCHMARK.json; the harness finds them, and no file that was there changes."""
    shutil.copytree(harness.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = tmp_path / "benchmarks" / "chip"
    before = _digest_tree(bench)

    config = dict(harness.load_json(bench / "configs" / "stablelm-3b.json"), name="dummy-model")
    (bench / "configs" / "dummy-model.json").write_text(json.dumps(config))
    traffic = dict(harness.traffic_of("train", bench), seq_len=4096, batch=2)
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(traffic))
    (bench / "limits" / "dummy-model.dummy_mix.json").write_text(json.dumps({"loss": 1e-3}))
    (bench / "metrics" / "dummy.steps.py").write_text("def read(ctx):\n    return len(ctx.steps) or None\n")

    spec = harness.load_spec(tmp_path)
    spec["configs"].append({"name": "dummy-model", "source": "https://example.org/dummy",
                            "file": "benchmarks/chip/configs/dummy-model.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy-model.dummy_mix", "config": "dummy-model",
                              "traffic": "dummy_mix", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("dummy-model.dummy_mix")
    spec["per_layer"].append({"name": "dummy.steps", "unit": "steps", "better": "higher",
                              "source": "program_counter", "layer": "train step",
                              "moves": "train_tokens_per_s", "workloads": ["dummy-model.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = harness.load_spec(tmp_path)
    cell = harness.cell_of(spec, "dummy-model.dummy_mix")
    assert harness.config_of(spec, cell["config"], tmp_path)["name"] == "dummy-model"
    assert harness.traffic_of(cell["traffic"], bench)["seq_len"] == 4096
    assert harness.limits_of(cell["name"], bench) == {"loss": 1e-3}
    wanted = {m["name"] for m in harness.metrics_for(spec, cell["name"], True)}
    assert "dummy.steps" in wanted
    reader = harness.metric_reader("dummy.steps", bench)
    assert reader(type("Ctx", (), {"steps": [{}, {}]})()) == 2
    assert all(_digest_tree(bench).get(p) == h for p, h in before.items())


DUMMY_JOB = """from chipbench.training import Result


def run(run):
    run.setup_done()
    with run.window():
        pass
    return Result(e2e={"train_tokens_per_s": 1.0}, attempted=1)
"""


def test_a_new_job_is_a_new_file_only(tmp_path):
    """A mix whose job is new: the job is one new file under jobs/, found by
    the name its traffic file gives, and a run of its cell makes a result line."""
    import argparse
    import time

    import jax

    shutil.copytree(harness.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmarks" / "chip"
    before = _digest_tree(bench)
    (bench / "jobs" / "dummy_job.py").write_text(DUMMY_JOB)
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps({"job": "dummy_job"}))
    (bench / "limits" / "olmoe-1b-7b.dummy_mix.json").write_text("{}")
    spec = harness.load_spec()
    spec["workloads"].append({"name": "olmoe-1b-7b.dummy_mix", "config": "olmoe-1b-7b",
                              "traffic": "dummy_mix", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("olmoe-1b-7b.dummy_mix")
    args = argparse.Namespace(workload="olmoe-1b-7b.dummy_mix", seed=2**40 + 1, seconds=0.1, trace=0)
    line = harness.run_cell(args, t_start=time.perf_counter(), spec=spec, bench=bench,
                            chip_check=lambda chips: jax.devices(), compile_cache=False)
    assert line["correct"] and line["attempted"] == 1
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(_digest_tree(bench).get(p) == h for p, h in before.items())


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_no_result_without_a_chip(tmp_path, where):
    """On the CPU, and in a directory that holds only BENCHMARK.json and the
    benchmark's own files, a run exits non-zero and prints nothing on stdout."""
    if where == "alone":
        shutil.copytree(harness.BENCH, tmp_path / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        cwd = tmp_path
    else:
        cwd = ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "olmoe-1b-7b.train",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
