"""Kernels: ``nack_mark``'s share of the HBM roofline, in %: the minimum
bytes it must move (``bench.kernel_bytes``, one call per executed
batch tick on each chip) over the peak HBM bandwidth, divided by its
device time in the trace, pooled over the cell's chips."""
from bench import kernel_bytes
from bench.metrics import common

KERNEL = "nack_mark"


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    ns = sum(common.kernel_ns(p, KERNEL) for p in common.planes(ctx))
    if ns == 0:
        return None
    sh = common.shapes(ctx)
    return kernel_bytes.roofline(
        KERNEL, sh["lanes"], sh["flows"], sh["words"], sh["nack_lanes"],
        int(common.block_ticks(ctx).sum()), ns / 1e9,
        ctx["peaks"]["hbm_bytes_per_s"])
