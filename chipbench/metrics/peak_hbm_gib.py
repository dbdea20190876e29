"""``peak_bytes_in_use`` read after the window, on the fullest chip of the
cell, in GiB."""


def read(red, ctx):
    return ctx.peak_bytes / 2 ** 30
