"""Tick: the share of leaf-op device time in XLA custom fusions
(``kind=kCustom``: the scatters and gathers of the packet queues and
lanes), over the cell's chips."""
from bench import trace_reduce as tr
from bench.metrics import common


def read(ctx):
    if ctx["trace"] is None:
        return None
    c = tr.by_class([op for p in common.planes(ctx) for op in p["ops"]])
    total = sum(c.values())
    return c["custom fusion (scatter/gather)"] / total if total else None
