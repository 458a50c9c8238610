"""Tick: device time under the program's ``tick.enqueue.write`` scope
(the enqueue's write of the candidates' packet records into the queue
rings, part of ``tick.enqueue``) per executed batch tick, in ms,
averaged over the cell's chips; the ticks are the driver's chunk
counter's (``bench.phase_reduce``). None where no device op lies under
the scope: a program that does not name the write."""
from bench import phase_reduce

SCOPE = "tick.enqueue.write"


def read(ctx):
    red = phase_reduce.for_ctx(ctx)
    if red is None or not any(SCOPE in phase_reduce.phase_ns(p)
                              for p in red["devices"]):
        return None
    return phase_reduce.ms_per_batch_tick(ctx, SCOPE)
