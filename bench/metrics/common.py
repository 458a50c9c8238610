"""Shared arithmetic of the per-layer metric readers.

A reader gets ``ctx``: ``cell`` (its configuration and traffic),
``calls`` (the window's ``simulate_batch`` calls, with each lane's
horizon), ``trace`` (``bench.trace_reduce.reduce`` of the traced
window, or None), ``devices`` (chips the cell runs on) and ``peaks``
(this device kind's entry of ``bench/peaks.json``, or None).
"""
from __future__ import annotations

import numpy as np

from bench import sweep
from bench import trace_reduce as tr


def window_ns(red: dict) -> int:
    """Length of the traced window on the host clock: the traced calls'
    spans. Only lengths are taken from the host clock, so nothing
    depends on how the profiler aligns host and device timelines; every
    device event in the trace belongs to these calls."""
    return sum(b - a for name, a, b in red["spans"]
               if name.startswith("bench.call."))


def planes(ctx) -> list:
    """The device planes of the chips the cell ran on."""
    return ctx["trace"]["devices"]


def block_ticks(ctx) -> np.ndarray:
    """[devices] executed batch ticks of each device's contiguous lane
    block, summed over the window's calls: a device's loop runs until
    its own slowest lane stops."""
    n = ctx["devices"]
    out = np.zeros(n, np.int64)
    for c in ctx["calls"]:
        h = c.horizons
        for d, block in enumerate(np.array_split(h, n)):
            out[d] += int(block.max())
    return out


def busy_ns(plane) -> int:
    """Union of the plane's leaf-op spans."""
    return tr.union_ns([(s, t) for _, s, t in plane["ops"]])


def module_ns(plane) -> int:
    """Union of the plane's XLA module spans."""
    return tr.union_ns(plane["modules"])


def kernel_ns(plane, kernel: str) -> int:
    return sum(t - s for cls, s, t in plane["ops"]
               if tr.kernel_of(cls) == kernel)


def shapes(ctx) -> dict:
    """Flows, ring words, NACK lanes and lanes per device of the cell."""
    cfg = ctx["cell"].cfg
    F = len(sweep.flow_table(cfg["collective"])["src"])
    Q = sweep.tree_of(cfg).num_queues
    return {"flows": F, "words": cfg["params"]["mp_range"] // 32,
            "nack_lanes": Q + 2 * F,
            "lanes": int(ctx["cell"].traffic["batch"]) // ctx["devices"]}
