"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs real steps on the local device set (CPU container: smoke-scale configs;
TPU fleet: the production mesh), wiring together every substrate:

    config → model → data pipeline → sharded train step → SCISPACE
    checkpointing (local-write + MEU) → fault-tolerant loop.

Example (CPU, ~100M-param quickstart is examples/train_end_to_end.py):
    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma2-2b --smoke --steps 50 --mesh 1,1 --global-batch 8
"""

from __future__ import annotations

import argparse
import json
import sys

import jax

from repro.configs import SHAPES, get_config, smoke_variant
from repro.core import Collaboration
from repro.data import ShardedPipeline, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.models import encdec as _encdec
from repro.optim import AdamW, AdamWConfig
from repro.train import CheckpointManager, Trainer, TrainerConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="1,1", help="data,model[,pod-major] e.g. 2,2 or 2,2,2")
    ap.add_argument("--cross-pod", default="auto", choices=["auto", "manual", "compressed"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--run", default="cli-run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    model = Model(cfg)

    shape = tuple(int(x) for x in args.mesh.split(","))
    mesh = make_mesh(shape)
    opt = AdamW(
        AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps)
    )
    frames = None
    patches = None
    if cfg.is_encdec:
        frames = (_encdec.enc_len_for(cfg, args.seq_len), cfg.frontend_dim)
    if cfg.frontend == "vision":
        patches = (cfg.frontend_tokens, cfg.frontend_dim)
    pipe = ShardedPipeline(
        SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq_len, period=16, vocab_eff=256),
        global_batch=args.global_batch,
        frames_shape=frames,
        patches_shape=patches,
    )

    ckpt = None
    if args.ckpt_every:
        collab = Collaboration()
        collab.add_datacenter("pod0", n_dtns=2)
        ckpt = CheckpointManager(collab, run=args.run, home_dc="pod0")

    trainer = Trainer(
        model,
        opt,
        mesh,
        pipe,
        TrainerConfig(
            microbatches=args.microbatches,
            loss_chunk=min(args.seq_len, 256),
            cross_pod=args.cross_pod,
            ckpt_every=args.ckpt_every,
        ),
        ckpt=ckpt,
        seed=args.seed,
    )
    result = trainer.run(args.steps)
    losses = [m["loss"] for m in trainer.metrics_log if "loss" in m]
    print(json.dumps({**result, "first_loss": losses[0], "last_loss": losses[-1]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
