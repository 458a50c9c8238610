"""Kernels: device time of the three hot-path Pallas kernels
(``sack_fused``, ``nack_mark``, ``sack_advance``) per executed batch
tick, in ms, averaged over the cell's chips."""
import numpy as np

from bench import trace_reduce as tr
from bench.metrics import common


def read(ctx):
    if ctx["trace"] is None:
        return None
    ticks = common.block_ticks(ctx)
    per = [sum(common.kernel_ns(p, k) for k in tr.KERNELS) / t
           for p, t in zip(common.planes(ctx), ticks) if t > 0]
    if not per or not any(per):
        return None
    return float(np.mean(per)) / 1e6
