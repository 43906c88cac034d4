"""Mean seconds of the SDS query for the newest step (the benchmark's span around ``latest_step``)."""


def read(ctx):
    return ctx.mean(ctx.span_seconds("ckpt.latest_step"))
