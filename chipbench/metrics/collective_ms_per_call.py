"""Device time of the collective operations (the butterfly's exchanges
between chips) on the busiest device, per factorize call, in ms.  None
where the trace holds no collective operation."""


def read(red, ctx):
    ns = red.busiest().collective_ns
    return ns / 1e6 / ctx.calls if ns else None
