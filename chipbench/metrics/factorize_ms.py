"""Time per factorize call, in ms: the whole window over the calls
completed in it."""


def read(red, ctx):
    return ctx.window_s / ctx.calls * 1e3 if ctx.calls else None
