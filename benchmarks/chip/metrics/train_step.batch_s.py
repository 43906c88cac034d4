"""Mean seconds a step waits for its batch: ``train.batch`` (``batch_at`` and
``device_put``) over the number of ``train.step`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per(ctx, "train.batch", "train.step")
