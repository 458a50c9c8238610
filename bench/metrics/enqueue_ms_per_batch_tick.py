"""Tick: device time under the program's ``tick.enqueue`` scope (the
queue scatter, overflow, and the gray-loss draw nested in it as
``tick.enqueue.loss``) per executed batch tick, in ms, averaged over the
cell's chips; the ticks are the driver's chunk counter's
(``bench.phase_reduce``)."""
from bench import phase_reduce


def read(ctx):
    return phase_reduce.ms_per_batch_tick(ctx, "tick.enqueue")
