"""Mean seconds a save spends in the store: ``store.write`` (the backend write and
the de-sync xattr) over the number of ``ckpt.save`` spans."""

from chipbench import program_spans


def read(ctx):
    return program_spans.per(ctx, "store.write", "ckpt.save")
