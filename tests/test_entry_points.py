"""Entry points on the CPU: the train launcher, compile-cache placement, and
``chip_smoke.py`` — its refusal to run without a TPU, and its phases at a
tiny size (the script itself only ever runs them on a chip)."""

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import ARCHS, smoke_variant
from repro.launch import compile_cache
from tests._multidev import run_multidev

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def default_cache_dir(tmp_path, monkeypatch):
    """Point the default cache at a temporary checkout root; cache everything."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", tmp_path / ".jax_cache")
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    yield tmp_path / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    compilation_cache.reset_cache()


def test_default_cache_dir_is_fixed_at_the_checkout_root():
    assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


def test_train_main_runs_smoke_on_1x1_mesh(default_cache_dir, capsys):
    from repro.launch import train

    rc = train.main(
        ["--arch", "stablelm-3b", "--smoke", "--steps", "3", "--mesh", "1,1", "--ckpt-every", "2"]
    )
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["final_step"] == 3 and out["restarts"] == 0
    assert math.isfinite(out["last_loss"])
    assert any(default_cache_dir.iterdir()), "no compile-cache entries at the default path"


def test_cache_env_dir_wins(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX caches there and the
    helper sets no other directory."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_to_run_without_tpu(tmp_path, where):
    """On the CPU, and in a directory with nothing of the repository but the
    script, it exits non-zero and prints no result."""
    if where == "alone":
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        cwd = tmp_path
    else:
        cwd = ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_chip_smoke_phases_at_tiny_size(capsys):
    import chip_smoke

    cfg = smoke_variant(ARCHS["stablelm-3b"])
    clock = chip_smoke.CompileClock()
    params = chip_smoke.train_and_resume(
        cfg, chip_smoke.one_chip_mesh(), clock, batch=2, seq=64, steps=2
    )
    chip_smoke.serve_and_check(
        cfg, params, clock, slots=2, max_len=64, prompt_lens=(5, 9), max_new=4
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["phase"] for l in lines] == ["train", "resume", "serve"]
    assert lines[1]["resumed_loss"] == lines[1]["uninterrupted_loss"]


def test_chip_smoke_four_chip_phase_on_virtual_devices():
    out = run_multidev(
        f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        from repro.configs import ARCHS, smoke_variant
        cfg = smoke_variant(ARCHS["stablelm-3b"])
        chip_smoke.four_chip_resume_on_one(
            cfg, chip_smoke.CompileClock(), batch=4, seq=64, steps=2)
        """,
        devices=4,
        timeout=300,
    )
    assert '"phase": "resume_1chip"' in out
