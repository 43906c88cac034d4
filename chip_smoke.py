"""Run the training and serving path once on a TPU, end to end.

    python3 chip_smoke.py               # one chip: train, resume through the workspace, serve
    python3 chip_smoke.py --chips 4     # four chips: train on a (data=2, model=2) mesh,
                                        # resume that checkpoint on one chip, compare

Configuration: ``stablelm-3b`` at its published widths (d_model 2560, 32
heads of 80, d_ff 6912, vocab 50304, partial rotary 0.25) with its depth cut
from 32 to 4 layers so that f32 weights and AdamW state fit one v5e chip's
16 GB.  Weights come from ``--seed``; the data is :class:`SyntheticLM`.

Phases, each through the repository's own objects:

- **train** — :class:`Trainer` with a native (LW+MEU)
  :class:`CheckpointManager` on a two-DC :class:`Collaboration` (``pod0``,
  ``pod1``) takes N steps, saves step N, and takes step N+1;
- **resume** — a second manager homed at ``pod1`` finds step N by an SDS
  query and restores it onto the chip; the restored bytes equal the saved
  ones and step N+1 from them repeats the uninterrupted loss bit for bit;
- **serve** — :class:`ServeEngine` answers 8 requests; one request's decode
  logits, read from the engine's own cache, agree with a full forward pass.

Each phase prints one JSON line: smoke output, not benchmark numbers.  The
last line is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before any phase runs; a failed check raises and exits
non-zero without that line.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import resource
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "stablelm-3b"
LAYERS = 4            # of 32: the most that fits 4 × 2048 training on one chip
BATCH, SEQ = 4, 2048
STEPS = 3             # N: save after step N, then take step N+1
SHARDS = 8            # checkpoint files per step: bounds the host copies a save makes
SLOTS, MAX_LEN, MAX_NEW = 8, 2048, 32
PROMPT_LENS = (128, 256, 512, 128, 256, 512, 128, 256)  # three distinct prefill shapes

#: Decode logits vs a full forward pass, as a share of the largest |logit|.
#: Both paths compute in bf16 (relative step 2^-8 ≈ 0.4%) but in different
#: orders: a one-row cache-attention decode against chunked flash attention
#: over the whole sequence.  2% is about five bf16 steps at the top of the
#: range — far below what a wrong cache row or position produces.
LOGIT_TOL = 0.02
#: 4-chip against 1-chip loss at step N+1 from the same checkpoint: the
#: model-parallel split changes the order of f32 reductions and the rounding
#: of bf16 activations; the mean over 8192 tokens keeps the gap well inside.
LOSS_RTOL = 5e-3


def check(ok: bool, what) -> None:
    """A smoke check; unlike ``assert`` it also holds under ``python -O``."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Seconds JAX spends compiling (or fetching from the persistent cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.total += duration

    def since(self, start: float) -> float:
        return self.total - start


def host_peak_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def device_peak_gb() -> float:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return max(peaks) / 1e9


def digests(tree) -> list:
    """One blake2b digest per leaf, copied to the host one leaf at a time."""
    return [
        hashlib.blake2b(np.ascontiguousarray(np.asarray(leaf)).view(np.uint8)).hexdigest()
        for leaf in jax.tree.leaves(tree)
    ]


def smoke_config():
    from repro.configs import get_config

    return get_config(ARCH).replace(n_layers=LAYERS)


def one_chip_mesh():
    from repro.launch.mesh import make_mesh

    return make_mesh((1, 1), ("data", "model"))


def make_trainer(cfg, mesh, collab, *, batch, seq, steps, seed, home_dc="pod0", save=True):
    from repro.data import ShardedPipeline, SyntheticLM
    from repro.models.model import Model
    from repro.optim import AdamW, AdamWConfig
    from repro.train import CheckpointManager, Trainer, TrainerConfig

    opt = AdamW(AdamWConfig(peak_lr=1e-4, warmup_steps=1, total_steps=steps + 1))
    pipe = ShardedPipeline(
        SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, seed=seed), global_batch=batch
    )
    ckpt = CheckpointManager(
        collab, run="chip-smoke", home_dc=home_dc, mode="native", n_shards=SHARDS
    )
    return Trainer(
        Model(cfg), opt, mesh, pipe,
        TrainerConfig(loss_chunk=min(seq, 256), ckpt_every=steps if save else 0),
        ckpt=ckpt, seed=seed,
    )


def losses(trainer) -> list:
    return [m["loss"] for m in trainer.metrics_log if "loss" in m]


def resume_at(trainer, collab, step: int):
    """Drop ``trainer``'s state, then restore ``step`` through a ``pod1`` manager."""
    from repro.train import CheckpointManager, init_state_abstract

    trainer.state = None  # one copy of the state on the device at a time
    ckpt = CheckpointManager(
        collab, run="chip-smoke", home_dc="pod1", mode="native", n_shards=SHARDS
    )
    found = ckpt.latest_step()
    check(found == step, f"SDS query at pod1 found step {found}, expected {step}")
    t0 = time.perf_counter()
    trainer.state = jax.block_until_ready(
        ckpt.restore(
            init_state_abstract(trainer.model, trainer.optimizer), found,
            shardings=trainer.shardings,
        )
    )
    return time.perf_counter() - t0


def train_and_resume(cfg, mesh, clock, *, batch=BATCH, seq=SEQ, steps=STEPS, seed=0):
    """Train N steps, save, take step N+1; resume step N at pod1, retake N+1.

    Returns the trained parameters (on the device) for the serve phase.
    """
    from repro.core import Collaboration

    collab = Collaboration()
    collab.add_datacenter("pod0", n_dtns=2)
    collab.add_datacenter("pod1", n_dtns=2)
    c0 = clock.total
    trainer = make_trainer(cfg, mesh, collab, batch=batch, seq=seq, steps=steps, seed=seed)
    trainer.run(steps)
    rss_after_save = host_peak_rss_gb()
    saved = digests(trainer.state)
    trainer.run(steps + 1)
    train_losses = losses(trainer)
    check(len(train_losses) == steps + 1 and all(map(math.isfinite, train_losses)), train_losses)
    step_s = [m["seconds"] for m in trainer.metrics_log if "loss" in m]
    save = next(m for m in trainer.metrics_log if m.get("event") == "save")
    emit(
        "train",
        losses=train_losses,
        compile_s=clock.since(c0),
        first_step_s=step_s[0],
        warm_step_s=step_s[-1],
        save_s=save["seconds"],
        save_bytes=save["bytes"],
        host_peak_rss_after_save_gb=rss_after_save,
        host_peak_rss_gb=host_peak_rss_gb(),
        device_peak_gb=device_peak_gb(),
    )

    restore_s = resume_at(trainer, collab, steps)
    restored = digests(trainer.state)
    check(restored == saved, "restored leaves differ from the saved ones")
    trainer.run(steps + 1)
    resumed = losses(trainer)[-1]
    check(resumed == train_losses[-1], (resumed, train_losses[-1]))
    emit(
        "resume",
        home_dc="pod1",
        step=steps,
        leaves_identical=len(saved),
        restore_s=restore_s,
        uninterrupted_loss=train_losses[-1],
        resumed_loss=resumed,
        host_peak_rss_gb=host_peak_rss_gb(),
        device_peak_gb=device_peak_gb(),
    )
    params = trainer.state["params"]
    trainer.state = None
    collab.close()
    return params


def serve_and_check(cfg, params, clock, *, slots=SLOTS, max_len=MAX_LEN,
                    prompt_lens=PROMPT_LENS, max_new=MAX_NEW, seed=0):
    """Serve one request per slot twice (cold, then warm); check one request's
    decode logits against a full forward pass over the same tokens."""
    from repro.models.model import Model
    from repro.models.transformer import _logits, lm_hidden
    from repro.serve import ServeConfig, ServeEngine

    check(len(prompt_lens) == slots, "one request per slot")  # request i lands in slot i
    model = Model(cfg)
    eng = ServeEngine(
        model, params, ServeConfig(max_len=max_len, slots=slots, eos_token=-1, seed=seed)
    )
    rng = np.random.default_rng(seed)

    def serve_round():
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, size=n), max_new) for n in prompt_lens]
        stats = eng.run_until_drained(reqs)
        check(all(r.done and len(r.out_tokens) == max_new for r in reqs), "every request served")
        return reqs, stats

    c0 = clock.total
    _, cold = serve_round()
    compile_s = clock.since(c0)
    reqs, warm = serve_round()

    i = 0
    req = reqs[i]
    pos = len(req.prompt) + max_new - 1  # the last emitted token is not in the cache yet
    check(int(eng.slot_pos[i]) == pos, (int(eng.slot_pos[i]), pos))
    tokens = np.zeros((slots, 1), np.int32)
    tokens[i, 0] = req.out_tokens[-1]
    _, dec = jax.jit(model.decode_step)(
        eng.params, eng.cache, jnp.asarray(tokens), jnp.asarray(eng.slot_pos)
    )
    full = np.concatenate([req.prompt, np.asarray(req.out_tokens, np.int32)])[None]
    forward = jax.jit(lambda p, t: _logits(p, lm_hidden(p, {"tokens": t}, cfg)[0][:, -1:], cfg))
    ref = np.asarray(forward(eng.params, jnp.asarray(full)), np.float32)[0, 0]
    got = np.asarray(dec, np.float32)[i, 0]
    check(np.isfinite(got).all() and np.isfinite(ref).all(), "finite logits")
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    check(err <= LOGIT_TOL * scale, (err, scale))
    emit(
        "serve",
        requests=len(reqs),
        new_tokens=int(warm["tokens"]),
        compile_s=compile_s,
        cold_tok_per_s=cold["tok_per_s"],
        warm_tok_per_s=warm["tok_per_s"],
        logits_max_abs_err=err,
        logits_max_abs=scale,
        logits_tol=LOGIT_TOL * scale,
        argmax_agree=bool(got.argmax() == ref.argmax()),
        device_peak_gb=device_peak_gb(),
    )


def four_chip_resume_on_one(cfg, clock, *, batch=BATCH, seq=SEQ, steps=STEPS, seed=0):
    """Train on a (data=2, model=2) mesh, save step N through pod0, take step
    N+1; restore step N at pod1 onto a 1-chip mesh and retake step N+1."""
    from repro.core import Collaboration
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    collab = Collaboration()
    collab.add_datacenter("pod0", n_dtns=2)
    collab.add_datacenter("pod1", n_dtns=2)
    c0 = clock.total
    mesh4 = make_mesh((2, 2), ("data", "model"))
    t4 = make_trainer(cfg, mesh4, collab, batch=batch, seq=seq, steps=steps, seed=seed)
    t4.run(steps + 1)
    l4 = losses(t4)
    check(len(l4) == steps + 1 and all(map(math.isfinite, l4)), l4)
    emit(
        "train_4chip",
        mesh={"data": 2, "model": 2},
        losses=l4,
        compile_s=clock.since(c0),
        warm_step_s=t4.metrics_log[-1]["seconds"],
        device_peak_gb=device_peak_gb(),
    )
    t4.state = None
    del t4

    t1 = make_trainer(cfg, one_chip_mesh(), collab, batch=batch, seq=seq, steps=steps,
                      seed=seed, home_dc="pod1", save=False)
    restore_s = resume_at(t1, collab, steps)
    t1.run(steps + 1)
    l1 = losses(t1)[-1]
    check(math.isfinite(l1) and abs(l1 - l4[-1]) <= LOSS_RTOL * abs(l4[-1]), (l1, l4[-1]))
    emit(
        "resume_1chip",
        step=steps,
        restore_s=restore_s,
        loss_4chip=l4[-1],
        loss_1chip=l1,
        rel_diff=abs(l1 - l4[-1]) / abs(l4[-1]),
        rtol=LOSS_RTOL,
    )
    collab.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import Model

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    cfg = smoke_config()
    n_params = sum(int(x.size) for x in jax.tree.leaves(Model(cfg).init_abstract()))
    emit(
        "config",
        arch=ARCH,
        n_layers=cfg.n_layers,
        published_layers=get_config(ARCH).n_layers,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        head_dim=cfg.head_dim,
        d_ff=cfg.d_ff,
        vocab=cfg.vocab_size,
        params=n_params,
        batch=BATCH,
        seq=SEQ,
        seed=args.seed,
        chips=args.chips,
        compile_cache=cache_dir,
    )
    if args.chips == 4:
        four_chip_resume_on_one(cfg, clock, seed=args.seed)
    else:
        params = train_and_resume(cfg, one_chip_mesh(), clock, seed=args.seed)
        serve_and_check(cfg, params, clock, seed=args.seed)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
