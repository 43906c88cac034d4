"""Model FLOPs of the window's train steps over their step seconds × chips × peak.

Step seconds are the trainer's own (after ``block_until_ready``); the FLOPs
are the benchmark's count of what the model needs (``chipbench/flops.py``).
"""


def read(ctx):
    if not ctx.steps or ctx.peaks is None:
        return None
    busy = sum(r["seconds"] for r in ctx.steps)
    work = ctx.step_flops * len(ctx.steps)
    return 100.0 * work / (busy * ctx.chips * ctx.peaks["bf16_flops_per_s"])
