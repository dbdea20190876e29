"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the device's operation intervals) / window, the
highest over the cell's devices."""


def read(red, ctx):
    return red.idle_share() * 100.0
