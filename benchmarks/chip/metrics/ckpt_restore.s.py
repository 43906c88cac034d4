"""Mean seconds of a restore onto the chip (the benchmark's span around ``restore``)."""


def read(ctx):
    return ctx.mean(ctx.span_seconds("ckpt.restore"))
