"""Share of the window in which no op ran on the device, from the profiler trace."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace["idle_share"]
