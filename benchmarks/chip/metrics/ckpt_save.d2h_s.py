"""Mean seconds of a save's device-to-host copy of the state (``ckpt.d2h`` spans)."""

from chipbench import program_spans


def read(ctx):
    return program_spans.mean(ctx, "ckpt.d2h")
