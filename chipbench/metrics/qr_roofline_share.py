"""Share of the roofline of the whole factorization, in %: the least time
a chip of the cell could take for its share of the QR (chipbench/work.py,
from the shape alone, at the peaks of chipbench/peaks.py) over the
busiest device's busy time per call."""


def read(red, ctx):
    busy_s = red.busiest().busy_ns / 1e9 / ctx.calls
    least = ctx.least_time()
    ctx.notes["qr_roofline_bound"] = least.bound
    ctx.notes["qr_least_time_us"] = least.seconds * 1e6
    return least.seconds / busy_s * 100.0
