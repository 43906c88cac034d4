"""Job ``resume``: resume across data centers, then check.

Set-up trains step 1, saves it at the home DC and takes step 2
uninterrupted; both steps go through the trainer's own call and are
compared with the plain reference after the window.  The window runs
``cycles`` resumes at ``resume_dc`` (or resumes until ``--seconds`` have
passed): drop the state, SDS discovery, cross-DC restore onto the chip, one
step.  Every resumed loss and the final state must equal the uninterrupted
ones bit for bit.
"""

from __future__ import annotations

import jax

from chipbench import data, flops, program, spans, training, weights
from chipbench.training import Check, Result


def run(run) -> Result:
    c, t, seed = run.config, run.traffic, run.seed
    res = Result(step_flops=flops.train_step(c, t["batch"], t["seq_len"]))
    cfg = program.model_config(c)
    key = weights.key_for(seed)
    batches = data.TokenBatches(seed, t, c["vocab_size"])
    saved = 1
    if t["reference_steps"] != saved + 1:
        raise ValueError(f"a resume cell compares steps 1-{saved + 1} with the reference")
    tr = program.trainer(cfg, t, batches, ckpt_every=saved)
    program.load_state(tr, lambda k: weights.make_params(c, k), key)
    collab = program.collaboration(t)
    tr.ckpt = program.checkpoint_manager(collab, t, t["ckpt"]["home_dc"])
    # step 1 is saved at the home DC; step 2 is the uninterrupted next step
    prog = training.first_steps(tr, c, t, key, after_first=lambda: setattr(tr, "ckpt", None))
    want_loss = prog["losses"][-1]
    want_state = training.digests(tr.state)
    abstract = program.abstract_state(tr)
    run.setup_done()

    found_gap, loss_gap = 0.0, 0.0
    with run.window() as w:
        while not training.window_over(run, w, res.attempted):
            with spans.span("resume"):
                tr.state = None
                mgr = program.checkpoint_manager(collab, t, t["resume_dc"])
                with spans.span("ckpt.latest_step"):
                    found = mgr.latest_step()
                with spans.span("ckpt.restore"):
                    tr.state = jax.block_until_ready(
                        mgr.restore(abstract, found, shardings=tr.shardings))
                with spans.span("trainer.run[step]"):
                    tr.run(found + 1)
                mgr.ws.close()
            got = tr.metrics_log[-1]["loss"]
            res.attempted += 1
            res.failed += got != want_loss
            found_gap = max(found_gap, abs(found - saved))
            loss_gap = max(loss_gap, abs(got - want_loss))
    res.steps, _ = training.window_rows(tr, len(tr.metrics_log) - res.attempted)
    res.e2e["resume_s"] = w.seconds / res.attempted
    res.memory_peak_bytes = training.memory_peak_bytes()
    differ = sum(a != b for a, b in zip(training.digests(tr.state), want_state))
    training.free(tr)
    collab.close()
    res.checks = training.reference_checks(run, prog) + [
        Check("found_step_gap", float(found_gap), run.limits["found_step_gap"],
              f"step found by SDS at {t['resume_dc']} against step {saved} saved"),
        Check("resumed_loss_gap", float(loss_gap), run.limits["resumed_loss_gap"],
              f"over {res.attempted} resumes, absolute"),
        Check("state_leaves_differ", float(differ), run.limits["state_leaves_differ"],
              f"of {len(want_state)} leaves after the resumed step"),
    ]
    return res
