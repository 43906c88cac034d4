"""Mean seconds a save held the trainer: device→host copy plus the whole save (trainer's save rows)."""


def read(ctx):
    return ctx.mean(r["seconds"] for r in ctx.saves)
