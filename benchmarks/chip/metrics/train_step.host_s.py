"""Mean seconds of a trainer loop iteration spent on the host: its ``train.iter`` span
less the ``train.step``, ``ckpt.d2h`` and ``ckpt.save`` spans inside it."""

from chipbench import program_spans


def read(ctx):
    return program_spans.self_mean(ctx, "train.iter", ("train.step", "ckpt.d2h", "ckpt.save"))
