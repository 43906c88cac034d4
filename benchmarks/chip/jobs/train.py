"""Job ``train``: train from the seed's weights, then check.

The first ``reference_steps`` steps go through the trainer's own call in
set-up and are compared with the plain reference after the window.  Without
a ``ckpt`` block the window runs steps until ``--seconds`` have passed.  With
one, it runs ``cycles`` whole cycles of ``every`` steps and one LW+MEU save,
deleting the older steps through the workspace; after the window the newest
step, found by SDS at ``readback_dc``, is read back and compared byte for
byte with the device state.
"""

from __future__ import annotations

import math
from typing import List

from chipbench import data, flops, program, spans, training, weights
from chipbench.training import Check, Result


def run(run) -> Result:
    c, t, seed = run.config, run.traffic, run.seed
    res = Result(step_flops=flops.train_step(c, t["batch"], t["seq_len"]))
    cfg = program.model_config(c)
    key = weights.key_for(seed)
    batches = data.TokenBatches(seed, t, c["vocab_size"])
    ck = t.get("ckpt")
    every = ck["every"] if ck else 0
    tr = program.trainer(cfg, t, batches, ckpt_every=every)
    program.load_state(tr, lambda k: weights.make_params(c, k), key)

    prog = training.first_steps(tr, c, t, key)
    tr.run(t["first_steps"])

    collab = mgr = None
    if ck:
        collab = program.collaboration(t)
        mgr = program.checkpoint_manager(collab, t, ck["home_dc"])
        tr.ckpt = mgr
    log0 = len(tr.metrics_log)
    run.setup_done()

    cycles = 0
    with run.window() as w:
        while not training.window_over(run, w, cycles):
            cycles += 1
            s = tr.current_step()
            if every:
                with spans.span(f"trainer.run[{every - 1} steps]"):
                    tr.run(s + every - 1)
                with spans.span("trainer.run[step + save]"):
                    tr.run(s + every)
                with spans.span("workspace.delete[older steps]"):
                    _delete_older(mgr, s + every)
            else:
                with spans.span("trainer.run[step]"):
                    tr.run(s + 1)
    res.steps, res.saves = training.window_rows(tr, log0)
    res.attempted = len(res.steps)
    res.failed = sum(not math.isfinite(r["loss"]) for r in res.steps)
    tokens = res.attempted * t["batch"] * t["seq_len"]
    res.e2e["train_tokens_per_s"] = tokens / w.seconds
    res.memory_peak_bytes = training.memory_peak_bytes()

    if ck:
        res.checks += _readback(tr, collab, t, run.limits)
        collab.close()
    training.free(tr)
    del tr
    res.checks = training.reference_checks(run, prog) + res.checks
    return res


def _delete_older(mgr, newest: int) -> None:
    """Delete every published step older than ``newest`` through the workspace.

    The SDS index keeps the rows of deleted files, so a row whose file is
    already gone is passed over."""
    for row in mgr.ws.search(f"run = {program.RUN}"):
        if int(row["attrs"]["step"]) < newest and mgr.ws.stat(row["path"]) is not None:
            mgr.ws.delete(row["path"])


def _readback(tr, collab, t, limits) -> List[Check]:
    """The newest step found by SDS at the other DC, read back, against the device state."""
    step = tr.current_step()
    on_device = training.digests(tr.state)
    other = program.checkpoint_manager(collab, t, t["readback_dc"])
    found = other.latest_step()
    if found is None:
        return [Check("readback_step_gap", float("inf"), limits["readback_step_gap"], "no step found")]
    read = other.restore(program.abstract_state(tr), found)
    differ = sum(a != b for a, b in zip(training.digests(read), on_device))
    other.ws.close()
    return [
        Check("readback_step_gap", float(abs(found - step)), limits["readback_step_gap"],
              f"found step {found}, device at step {step}"),
        Check("readback_leaves_differ", float(differ), limits["readback_leaves_differ"],
              f"of {len(on_device)} leaves"),
    ]
