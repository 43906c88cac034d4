"""Readings of the program, the control and the planted faults of a
``train_mla`` cell, on the chip.

    python3 benchmarks/chip/control_mla.py --workload <cell> --seeds 11 12 13 [--planted 3]

As ``control.py``, on the plain DeepSeek-V3-style reference
(:mod:`chipbench.reference_mla`) and with its extra number, ``bias_update``:
for each seed the cell's first steps are taken at the cell's own sizes
through the trainer's own call and feed (``program``), and on the first
``--planted`` seeds the reference is also read with every matmul in fp8
(``fp8``, the control), on half of each batch (``half_batch``), and as a
step that leaves its state unchanged (``unchanged``, computed, not run).
Each seed prints one JSON line: for each way, the numbers compared and
``correct``.  Without a TPU it exits non-zero, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def readings(tr, c, t, limits, seed: int, planted: bool = True):
    """{way: {number: value, "correct": bool}} for one seed; ``tr`` is the
    cell's trainer, whose state and data are replaced by the seed's."""
    import numpy as np

    from chipbench import data, program, reference, training, training_mla, weights, weights_mla

    key = weights.key_for(seed)
    tr.pipeline = data.TokenBatches(seed, t, c["vocab_size"])
    tr.metrics_log = []
    program.load_state(tr, lambda k: weights_mla.make_params(c, k), key)
    ways = {"program": training_mla.first_steps(tr, c, t, key)}
    training.free(tr)
    n = t["reference_steps"]
    ref = training_mla.reference_readings(c, t, seed, n)
    if planted:
        ways["fp8"] = training_mla.reference_readings(c, t, seed, n, cast=reference.fp8)
        ways["half_batch"] = training_mla.reference_readings(c, t, seed, n, rows=slice(0, t["batch"] // 2))
        ways["unchanged"] = dict(ref, losses=[ref["losses"][0]] * n,
                                 grad_leaf=np.zeros_like(ref["grad_leaf"]),
                                 delta_leaf=np.zeros_like(ref["delta_leaf"]),
                                 bias=np.zeros_like(ref["bias"]))
    out = {}
    for way, got in ways.items():
        checks = training_mla.compare(got, ref, limits, c)
        out[way] = {**{ch.name: ch.value for ch in checks}, "correct": all(ch.ok for ch in checks)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", type=int, default=3, help="seeds on which the control and faults are read")
    args = ap.parse_args(argv)

    from chipbench import harness, program, program_mla

    spec = harness.load_spec()
    cell = harness.cell_of(spec, args.workload)
    harness.require_chip(cell["chips"])
    harness.enable_compile_cache()
    c = harness.config_of(spec, cell["config"])
    t = harness.traffic_of(cell["traffic"])
    limits = harness.limits_of(cell["name"])
    tr = program.trainer(program_mla.model_config(c), t, None)
    for i, seed in enumerate(args.seeds):
        out = readings(tr, c, t, limits, seed, planted=i < args.planted)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
