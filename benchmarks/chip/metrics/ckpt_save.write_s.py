"""Mean seconds of a save's shard writes into the home DC's store (``write_s`` of the save rows)."""


def read(ctx):
    return ctx.mean(r["write_s"] for r in ctx.saves)
