"""Tick: the share of the run module's leaf-op time under no ``tick.*``
or ``driver.*`` scope (ops the compiler added, such as carry copies, or
code outside every named phase), pooled over the cell's chips
(``bench.phase_reduce``)."""
from bench import phase_reduce


def read(ctx):
    red = phase_reduce.for_ctx(ctx)
    if red is None:
        return None
    phases = [phase_reduce.phase_ns(p) for p in red["devices"]]
    return (sum(c[None] for c in phases)
            / sum(sum(c.values()) for c in phases))
