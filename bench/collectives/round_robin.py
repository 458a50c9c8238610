"""The round-robin all-to-all: rounds r = 1..n-1, in which rank i sends
ceil(S/n) packets to rank i+r, each of its rounds after its previous
one."""
from __future__ import annotations


def flows(kind: str, n: int, s: int) -> dict:
    if kind != "all_to_all":
        raise ValueError(f"round robin here is the all-to-all, not {kind!r}")
    c = -(-s // n)
    out = {"src": [], "dst": [], "size": [], "dep": []}
    for r in range(1, n):
        for i in range(n):
            out["src"].append(i), out["dst"].append((i + r) % n)
            out["size"].append(c)
            out["dep"].append(-1 if r == 1 else (r - 2) * n + i)
    return out
