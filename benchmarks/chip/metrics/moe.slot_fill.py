"""Share of the expert slots computed that held a choice: the ``moe_kept``
tags of the window's ``train.step`` spans over their ``moe_slots`` tags,
summed over the window (None where the program tags no step)."""

from chipbench import program_spans


def read(ctx):
    steps = [s for s in program_spans.window(ctx) or [] if s.name == "train.step" and s.tags
             and "moe_slots" in s.tags]
    slots = sum(s.tags["moe_slots"] for s in steps)
    return 100.0 * sum(s.tags["moe_kept"] for s in steps) / slots if slots else None
