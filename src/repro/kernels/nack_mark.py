"""Pallas TPU kernel: duplicate-safe NACK retransmit-bit marking
(Sec. 3.2.4).

Every simulator tick, each control-TC NACK lane asks for one bit —
(flow row, PSN offset) — to be OR-ed into the source retransmit bitmap.
Several lanes may target one flow, and two lanes may carry the SAME
(flow, offset) (a packet and its retransmission trimmed in the same
tick), so the combine is OR, not add.

TPU adaptation: a scatter is not available across lanes, so the mark is
re-expressed as a contraction. For an F-row block,

    hits[r, m] = sum_l rowhot[r, l] * (off[l] == m)

is an MXU-friendly [R, L] x [L, MP] matmul (counts are small integers,
exact in f32), and `hits > 0` collapses duplicates back to the OR
semantics. The bool plane then packs into uint32 ring words by two more
matmuls, [R, MP] x [MP, W], against place-value matrices holding the
low and the high 16 bits of each word: bits are distinct powers of two
per word, so each pack-sum IS the OR, and every product and partial sum
is an integer below 2^16, exact in f32. (Mosaic cannot split the lane
axis into [W, 32] to pack on the VPU, and reduces no unsigned integers.)

Block layout: (BLOCK_F rows) x (MP bit-lanes, a multiple of 128) per
grid step; the lane operands (flow / off / valid) ride along whole, one
value per padded row, column 0 — the same carrier layout the SACK
kernels use for per-row scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_F = 64
WORD = 32


def _nack_kernel(rtx_ref, flow_ref, off_ref, valid_ref, out_ref,
                 *, w: int, lanes: int, num_flows: int):
    rtx = rtx_ref[:, :w]                             # [R, W] uint32
    flow = flow_ref[...][:, 0]                       # [Lp] int32
    off = off_ref[...][:, 0]                         # [Lp] int32
    valid = valid_ref[...][:, 0] != 0                # [Lp]
    R = rtx.shape[0]
    mp = w * WORD

    lane_col = jax.lax.broadcasted_iota(jnp.int32, (R, flow.shape[0]), 1)
    valid = valid & (lane_col[0] < lanes) & (flow >= 0) & (flow < num_flows)

    # global row ids of this block
    f0 = pl.program_id(0) * BLOCK_F
    row = jax.lax.broadcasted_iota(jnp.int32, (R, flow.shape[0]), 0) + f0
    rowhot = (flow[None, :] == row) & valid[None, :]          # [R, Lp]

    m = jax.lax.broadcasted_iota(jnp.int32, (flow.shape[0], mp), 1)
    posmat = (jnp.clip(off, 0, mp - 1)[:, None] == m)         # [Lp, MP]
    hits = jnp.dot(rowhot.astype(jnp.float32), posmat.astype(jnp.float32),
                   preferred_element_type=jnp.float32)        # [R, MP]
    plane = (hits > 0.5).astype(jnp.float32)

    m = jax.lax.broadcasted_iota(jnp.int32, (mp, w), 0)
    bit = m % WORD
    in_word = (m // WORD) == jax.lax.broadcasted_iota(jnp.int32, (mp, w), 1)
    place = (1 << (bit % 16)).astype(jnp.float32)
    lo, hi = (jnp.dot(plane, jnp.where(in_word & half, place, 0.0),
                      preferred_element_type=jnp.float32).astype(jnp.int32)
              for half in (bit < 16, bit >= 16))                # [R, W]
    words = jax.lax.bitcast_convert_type((hi << 16) | lo, jnp.uint32)
    out_ref[:, :w] = rtx | words


@functools.partial(jax.jit, static_argnames=("interpret",))
def nack_mark(rtx: jax.Array, flow: jax.Array, off: jax.Array,
              valid: jax.Array, interpret: bool = False) -> jax.Array:
    """OR lane-requested retransmit bits into [F, W] uint32 rings.

    flow/off: [L] int32 (off is a PSN offset in [0, W*32)); valid: [L]
    bool. Invalid, out-of-range-row lanes mark nothing.
    ``interpret=True`` runs the body in the Pallas interpreter (CPU
    validation only).
    """
    f, w = rtx.shape
    lanes = flow.shape[0]
    assert w <= 32
    rows = -(-f // BLOCK_F) * BLOCK_F
    lrows = -(-lanes // 8) * 8
    rtx_p = jnp.pad(rtx, ((0, rows - f), (0, 128 - w)))
    lane_pad = ((0, lrows - lanes), (0, 127))
    flow_p = jnp.pad(flow.reshape(-1, 1), lane_pad)
    off_p = jnp.pad(off.reshape(-1, 1), lane_pad)
    valid_p = jnp.pad(valid.astype(jnp.int32).reshape(-1, 1), lane_pad)

    grid = (rows // BLOCK_F,)
    spec128 = pl.BlockSpec((BLOCK_F, 128), lambda i: (i, 0))
    lane_spec = pl.BlockSpec((lrows, 128), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_nack_kernel, w=w, lanes=lanes, num_flows=f),
        grid=grid,
        in_specs=[spec128, lane_spec, lane_spec, lane_spec],
        out_specs=spec128,
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
        interpret=interpret,
    )(rtx_p, flow_p, off_p, valid_p)
    return out[:f, :w]
