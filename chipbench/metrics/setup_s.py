"""Set-up, in s: from process start to the first timed call (imports,
device start, the data made on the device, the warm-up calls)."""


def read(red, ctx):
    return ctx.setup_s
