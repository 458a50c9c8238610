"""Tick (``fabric.make_step``) under the MoE dispatch: XLA module time on
the device per executed batch tick, in ms, averaged over the cell's
chips. The same reading as ``device_ms_per_batch_tick``, named for the
all-to-all cell so that its tick cost is tracked under its own metric."""
from bench.metrics.device_ms_per_batch_tick import read  # noqa: F401
