"""Tick (``fabric.make_step``): XLA module time on the device per
executed batch tick, in ms, averaged over the cell's chips."""
import numpy as np

from bench.metrics import common


def read(ctx):
    if ctx["trace"] is None or not ctx["trace"]["devices"]:
        return None
    ticks = common.block_ticks(ctx)
    per = [common.module_ns(p) / t
           for p, t in zip(common.planes(ctx), ticks) if t > 0]
    return float(np.mean(per)) / 1e6 if per else None
