"""Mean seconds a save spends serializing its shards: ``scidata.serialize`` over
the number of ``ckpt.save`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per(ctx, "scidata.serialize", "ckpt.save")
