"""Driver: device time under the program's ``driver.freeze`` scope (the
masked chunk body's carry-wide selects, with every fusion whose root is
one of them) per executed batch tick, in ms, averaged over the cell's
chips; the ticks are the driver's chunk counter's
(``bench.phase_reduce``)."""
from bench import phase_reduce


def read(ctx):
    return phase_reduce.ms_per_batch_tick(ctx, "driver.freeze")
