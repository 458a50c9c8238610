"""Tick: device time under the program's ``tick.control`` scope (the
control events, with the ``sack_fused`` and ``nack_mark`` kernels) per
executed batch tick, in ms, averaged over the cell's chips; the ticks
are the driver's chunk counter's (``bench.phase_reduce``)."""
from bench import phase_reduce


def read(ctx):
    return phase_reduce.ms_per_batch_tick(ctx, "tick.control")
