"""The flat switch-rooted tree all-reduce with in-network reduction off:
every non-root rank sends its whole vector to rank 0, and rank 0 sends
the result back to rank i once i's contribution has arrived."""
from __future__ import annotations


def flows(kind: str, n: int, s: int) -> dict:
    if kind != "all_reduce":
        raise ValueError(f"the tree here is the all-reduce, not {kind!r}")
    out = {"src": [], "dst": [], "size": [], "dep": []}
    for i in range(1, n):
        out["src"].append(i), out["dst"].append(0)
        out["size"].append(s), out["dep"].append(-1)
    for i in range(1, n):
        out["src"].append(0), out["dst"].append(i)
        out["size"].append(s), out["dep"].append(i - 1)
    return out
