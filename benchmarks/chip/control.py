"""Readings of the program, the control and the planted faults of a training cell, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11 12 13 [--planted 3]

For each seed the cell's first steps are taken at the cell's own sizes, and
compared with the plain float32 reference by the cell's own check and limits
(``limits/<cell>.json``), four ways:

- ``program``    — through the trainer's own call and feed, as a run takes them in set-up;
- ``fp8``        — the reference with every matmul in fp8 (the control);
- ``half_batch`` — the reference on half of each batch, the mean over the rest;
- ``unchanged``  — a step that returns its state unchanged (computed, not run:
                   the same losses as step 1 would repeat, and no change).

The last three are read on the first ``--planted`` seeds only.  Each seed
prints one JSON line: for each way, the numbers compared and ``correct``.
The program's readings set the lower end of each limit and must come out
correct; the others set the upper end and must come out not correct.  One
trainer serves every seed, so the program's dozen seeds and the control's
fit one process.  Without a TPU it exits non-zero, as ``run.py`` does.
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

    from chipbench import data, program, reference, training, weights

    key = weights.key_for(seed)
    tr.pipeline = data.TokenBatches(seed, t, c["vocab_size"])
    tr.metrics_log = []
    program.load_state(tr, lambda k: weights.make_params(c, k), key)
    ways = {"program": training.first_steps(tr, c, t, key)}
    training.free(tr)
    n = t["reference_steps"]
    ref = training.reference_readings(c, t, seed, n)
    if planted:
        ways["fp8"] = training.reference_readings(c, t, seed, n, cast=reference.fp8)
        ways["half_batch"] = training.reference_readings(c, t, seed, n, rows=slice(0, t["batch"] // 2))
        ways["unchanged"] = dict(ref, losses=[ref["losses"][0]] * n,
                                 grad_leaf=np.zeros_like(ref["grad_leaf"]),
                                 delta_leaf=np.zeros_like(ref["delta_leaf"]))
    paths = training.leaf_paths(c)
    out = {}
    for way, got in ways.items():
        checks = training.compare_training(got, ref, limits, paths)
        out[way] = {**{ch.name: ch.value for ch in checks}, "correct": all(ch.ok for ch in checks)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", type=int, default=3, help="seeds on which the control and faults are read")
    args = ap.parse_args(argv)

    from chipbench import harness, program

    spec = harness.load_spec()
    cell = harness.cell_of(spec, args.workload)
    harness.require_chip(cell["chips"])
    harness.enable_compile_cache()
    c = harness.config_of(spec, cell["config"])
    t = harness.traffic_of(cell["traffic"])
    limits = harness.limits_of(cell["name"])
    tr = program.trainer(program.model_config(c), t, None)
    for i, seed in enumerate(args.seeds):
        out = readings(tr, c, t, limits, seed, planted=i < args.planted)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
