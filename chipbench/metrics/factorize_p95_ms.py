"""95th percentile of every call's latency in the window, in ms (in an
open loop each latency is taken from when the call was due)."""
import numpy as np


def read(red, ctx):
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3 if ctx.calls else None
