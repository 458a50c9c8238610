"""Pallas TPU kernel: batched NSCC congestion-window update.

The paper bills UET as "potentially fully hardware-accelerated"; the NSCC
per-ACK control loop (Sec. 3.3.1) is the per-packet arithmetic a NIC does
at line rate. On a TPU-resident simulator the analogous hot spot is
updating *millions* of CCC windows per round — a pure VPU workload:
elementwise selects and FMAs over f32/i32 lanes.

Layout: the CCC pool is reshaped to [R, 128] (lane-aligned); the grid
blocks rows in chunks of BLOCK_R, so one program instance owns a
(BLOCK_R, 128) VMEM tile of every operand — comfortably below VMEM limits
(5 tiles x 8x128 x 4B = 160 KiB at BLOCK_R=8... we use 64 rows for fewer
grid steps: 5 x 64x128 x 4B = 160 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cms.nscc import NSCCParams

BLOCK_R = 64
LANES = 128


def _nscc_kernel(cwnd_ref, ecn_ref, rtt_ref, count_ref, out_ref, *,
                 base_rtt: float, target_factor: float, md: float,
                 quick_gain: float, ai: float, min_cwnd: float,
                 max_cwnd: float):
    cwnd = cwnd_ref[...]
    ecn = ecn_ref[...] != 0
    rtt = rtt_ref[...]
    count = count_ref[...].astype(jnp.float32)

    target = base_rtt * target_factor
    high = rtt > target
    overload = jnp.clip((rtt - target) / jnp.maximum(rtt, 1e-6), 0.0, 1.0)
    dec = -md * overload
    gap = jnp.clip((target - rtt) / target, 0.0, 1.0)
    quick = quick_gain * gap
    gentle = ai / jnp.maximum(cwnd, 1.0)
    delta = jnp.where(ecn, jnp.where(high, dec, 0.0),
                      jnp.where(high, gentle, quick))
    new = jnp.where(count > 0, cwnd + delta * count, cwnd)
    out_ref[...] = jnp.clip(new, min_cwnd, max_cwnd)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def nscc_update(cwnd: jax.Array, ecn: jax.Array, rtt: jax.Array,
                count: jax.Array, params: NSCCParams = NSCCParams(),
                interpret: bool = False) -> jax.Array:
    """Update N congestion windows in one fused VPU pass.

    Args:
      cwnd:  [N] float32
      ecn:   [N] bool/int32 — aggregated ECN-CE of this round's ACKs
      rtt:   [N] float32    — measured RTT (ticks or µs, caller's choice;
                              must match params.base_rtt units)
      count: [N] int32      — ACKed packets this round (0 = no update)
      interpret: run the kernel body in the Pallas interpreter (CPU
        validation only).
    """
    n = cwnd.shape[0]
    rows = -(-n // LANES)
    pad = rows * LANES - n

    def prep(x, dtype):
        x = jnp.asarray(x).astype(dtype)
        return jnp.pad(x, (0, pad)).reshape(rows, LANES)

    cw = prep(cwnd, jnp.float32)
    ec = prep(ecn, jnp.int32)
    rt = prep(rtt, jnp.float32)
    ct = prep(count, jnp.int32)

    grid = (-(-rows // BLOCK_R),)
    spec = pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0))
    kernel = functools.partial(
        _nscc_kernel, base_rtt=params.base_rtt,
        target_factor=params.target_factor, md=params.md,
        quick_gain=params.quick_gain, ai=params.ai,
        min_cwnd=params.min_cwnd, max_cwnd=params.max_cwnd)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(cw, ec, rt, ct)
    return out.reshape(-1)[:n]
