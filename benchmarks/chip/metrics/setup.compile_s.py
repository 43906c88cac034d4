"""Seconds JAX's backend compiles took during set-up (persistent-cache fetches included)."""


def read(ctx):
    return ctx.setup_compile_s
