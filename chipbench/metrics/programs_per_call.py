"""Device program executions in the traced window, on the busiest device,
per factorize call."""


def read(red, ctx):
    return red.busiest().programs / ctx.calls
