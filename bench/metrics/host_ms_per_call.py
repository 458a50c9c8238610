"""Host entry (``simulate_batch``: fault normalisation, transfers,
result assembly): the traced calls' time on the host clock less the time
the longest-running chip spent in XLA modules, in ms per call."""
from bench.metrics import common


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["devices"]:
        return None
    calls = sum(name.startswith("bench.call.") for name, _, _ in red["spans"])
    device = max(common.module_ns(p) for p in common.planes(ctx))
    return (common.window_ns(red) - device) / calls / 1e6
