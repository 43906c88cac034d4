"""Job ``train_mla``: train a DeepSeek-V3-style configuration (multi-head
latent attention, a leading dense layer, sigmoid-routed experts of which the
chip holds its expert-parallel share) from the seed's weights, then check.

As job ``train`` without a checkpoint: the first ``reference_steps`` steps
go through the trainer's own call in set-up and are compared with the plain
reference (:mod:`chipbench.reference_mla`) after the window, which runs
whole steps until ``--seconds`` have passed.
"""

from __future__ import annotations

import math

from chipbench import data, flops_mla, program, program_mla, spans, training, training_mla, weights, weights_mla
from chipbench.training import Result


def run(run) -> Result:
    c, t, seed = run.config, run.traffic, run.seed
    res = Result(step_flops=flops_mla.train_step(c, t["batch"], t["seq_len"]))
    cfg = program_mla.model_config(c)
    key = weights.key_for(seed)
    batches = data.TokenBatches(seed, t, c["vocab_size"])
    tr = program.trainer(cfg, t, batches)
    program.load_state(tr, lambda k: weights_mla.make_params(c, k), key)

    prog = training_mla.first_steps(tr, c, t, key)
    tr.run(t["first_steps"])
    log0 = len(tr.metrics_log)
    run.setup_done()

    with run.window() as w:
        while not training.window_over(run, w, 0):
            with spans.span("trainer.run[step]"):
                tr.run(tr.current_step() + 1)
    res.steps, res.saves = training.window_rows(tr, log0)
    res.attempted = len(res.steps)
    res.failed = sum(not math.isfinite(r["loss"]) for r in res.steps)
    res.e2e["train_tokens_per_s"] = res.attempted * t["batch"] * t["seq_len"] / w.seconds
    res.memory_peak_bytes = training.memory_peak_bytes()

    training.free(tr)
    del tr
    res.checks = training_mla.reference_checks(run, prog)
    return res
