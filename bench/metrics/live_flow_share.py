"""Tick: the share of the dense flow-lane work that live flows use:
ticks on which a flow was released (its ``dep`` source-complete) and not
yet source-complete itself, over flows x horizon, pooled over the
window's calls and lanes. Derived on the host from the program's
per-flow source-completion ticks, so it costs the chip nothing."""
import numpy as np

from bench import sweep


def read(ctx):
    dep = sweep.flow_table(ctx["cell"].cfg["collective"])["dep"]
    live = paid = 0
    for c in ctx["calls"]:
        for r in c.results:
            done = np.asarray(r.source_completion_ticks(), np.int64)
            if (done < 0).any():
                return None
            release = np.where(dep >= 0, done[np.maximum(dep, 0)], 0)
            live += int((done - release).sum())
            paid += len(done) * r.horizon
    return live / paid if paid else None
