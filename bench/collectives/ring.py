"""NCCL's ring all-reduce (NCCL-tests ``all_reduce_perf`` with
``NCCL_ALGO=Ring``): a reduce-scatter then an all-gather around the
ring, 2(n-1) phases of n flows; each phase moves one chunk of
ceil(S/n) packets from rank i to rank i+1, and flow (p, i) waits for
flow (p-1, i-1), the chunk that rank i must reduce or forward."""
from __future__ import annotations


def flows(kind: str, n: int, s: int) -> dict:
    if kind != "all_reduce":
        raise ValueError(f"the ring here is the all-reduce, not {kind!r}")
    c = -(-s // n)
    out = {"src": [], "dst": [], "size": [], "dep": []}
    for p in range(2 * (n - 1)):
        for i in range(n):
            out["src"].append(i)
            out["dst"].append((i + 1) % n)
            out["size"].append(c)
            out["dep"].append(-1 if p == 0 else (p - 1) * n + (i - 1) % n)
    return out
