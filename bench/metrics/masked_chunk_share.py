"""Driver: the share of the while loop's chunks that ran the masked body
(frozen lanes or the budget's last chunk), from the program's own chunk
counter (``SimResult.driver_chunks``), pooled over the window's calls
and lanes, so over chips by their lanes. Prints beside it, on stderr,
the share the lane horizons imply on one chip."""
import sys


def read(ctx):
    got = [r.driver_chunks for c in ctx["calls"] for r in c.results
           if getattr(r, "driver_chunks", None) is not None]
    if not got:
        return None
    share = sum(m for _, m in got) / sum(f + m for f, m in got)
    paid = sum(int(c.horizons.max()) for c in ctx["calls"])
    spread = sum(int(c.horizons.max() - c.horizons.min())
                 for c in ctx["calls"])
    print(f"masked_chunk_share: counter {share:.6f}, horizons "
          f"{spread / paid:.6f}", file=sys.stderr, flush=True)
    return share
