"""Device: 1 - the union of leaf-op spans over the traced window's
length on the host clock (the whole traced call, host work included),
averaged over the cell's chips."""
import numpy as np

from bench.metrics import common


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["devices"]:
        return None
    busy = np.mean([common.busy_ns(p) for p in common.planes(ctx)])
    return 1.0 - float(busy) / common.window_ns(red)
