"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Boots the batched prefill/decode engine with continuous batching and runs a
synthetic request stream, reporting token throughput.

Example:
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke \
        --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro.configs import get_config, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serve import ServeConfig, ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default=None, help="data,model e.g. 2,2 (default: no mesh)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    mesh = make_mesh(tuple(int(x) for x in args.mesh.split(","))) if args.mesh else None

    eng = ServeEngine(
        model,
        params,
        ServeConfig(
            max_len=args.max_len, slots=args.slots,
            temperature=args.temperature, eos_token=-1, seed=args.seed,
        ),
        mesh=mesh,
    )
    rng = np.random.default_rng(args.seed)
    reqs = [
        eng.submit(rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 24))), args.max_new)
        for _ in range(args.requests)
    ]
    stats = eng.run_until_drained(reqs)
    assert all(r.done for r in reqs)
    print(json.dumps(stats, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
