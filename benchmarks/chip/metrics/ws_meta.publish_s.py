"""Mean seconds of a save's MEU export and offline indexing (``publish_s`` of the save rows)."""


def read(ctx):
    return ctx.mean(r["publish_s"] for r in ctx.saves)
