"""Pallas TPU kernel: duplicate-safe NACK retransmit-bit marking
(Sec. 3.2.4).

Every simulator tick, each control-TC NACK lane asks for one bit —
(flow row, PSN offset) — to be OR-ed into the source retransmit bitmap.
Several lanes may target one flow, and two lanes may carry the SAME
(flow, offset) (a packet and its retransmission trimmed in the same
tick), so the combine is OR, not add.

TPU adaptation: a scatter is not available across lanes, so the mark is
re-expressed as a contraction over the L NACK lanes,

    hits[r, m] = sum_l rowhot[r, l] * pos[m, l],
    rowhot[r, l] = valid[l] & (flow[l] == r),  pos[m, l] = (off[l] == m),

an MXU matmul [R, L] x [L, MP], and `hits > 0` collapses duplicates back
to the OR semantics. The bool plane then packs into uint32 ring words by
two more matmuls, [R, MP] x [MP, 128], against place-value matrices
holding the low and the high 16 bits of each word: bits are distinct
powers of two per word, so each pack-sum IS the OR. (Mosaic cannot split
the lane axis into [W, 32] to pack on the VPU, and reduces no unsigned
integers.)

Every matmul runs in one MXU pass on bfloat16 operands with float32
accumulation, and is exact: the one-hots and the plane hold 0 or 1, the
place values are powers of two up to 2^15, all of which bfloat16 holds
exactly; every sum is an integer below 2^16 (a hit count is at most L,
a pack-sum at most 2^16 - 1), which float32 holds exactly.

Block layout: the grid is (row blocks, lane blocks), the lane blocks —
the contraction axis — innermost and sequential. The lane operands ride
lane-major, one int32 [8, LB] block per step (rows flow, off, valid), so
each step builds its block's rowhot [RB, LB] and pos [MP, LB] once, adds
their contraction into a float32 [RB, MP] VMEM accumulator, and the last
lane block packs it and writes `rtx | words` to the [RB, 128] output
block. The row block RB is all of F rounded up to 16 up to `MAX_ROWS`
rows, so at the fabric's widths each scenario builds each one-hot
element once; LB, a multiple of 128, is sized from RB and MP so that one
one-hot temporary stays near `ONEHOT_BYTES`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32
MAX_ROWS = 1024           # rows of one block: bounds the accumulator
ONEHOT_BYTES = 1 << 21    # an int32 one-hot temporary of one lane block


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _blocks(f: int, lanes: int, mpp: int) -> tuple[int, int, int, int]:
    """(row block, row blocks, lane block, lane blocks) for F rows, L
    lanes and MP bit-lanes: blocks as even as the alignment allows."""
    nr = _cdiv(f, MAX_ROWS)
    rb = _round_up(_cdiv(f, nr), 16)
    lb_max = max(ONEHOT_BYTES // (4 * max(rb, mpp)) // 128 * 128, 128)
    nk = _cdiv(lanes, lb_max)
    return rb, nr, _round_up(_cdiv(lanes, nk), 128), nk


def _one(mask):
    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)


def _nack_kernel(rtx_ref, lane_ref, out_ref, acc_ref,
                 *, lanes: int, num_flows: int, mp: int):
    i, k = pl.program_id(0), pl.program_id(1)
    rb, mpp = acc_ref.shape
    lb = lane_ref.shape[1]

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    flow = lane_ref[0:1, :]                                   # [1, LB]
    off = jnp.clip(lane_ref[1:2, :], 0, mp - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lb), 1) + k * lb
    ok = ((lane_ref[2:3, :] != 0) & (lane < lanes)
          & (flow >= 0) & (flow < num_flows))
    row = jax.lax.broadcasted_iota(jnp.int32, (rb, lb), 0) + i * rb
    rowhot = _one((row == flow) & ok)                         # [RB, LB]
    pos = _one(jax.lax.broadcasted_iota(jnp.int32, (mpp, lb), 0) == off)
    acc_ref[...] += jax.lax.dot_general(                      # [RB, MP]
        rowhot, pos, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        plane = _one(acc_ref[...] > 0.5)
        m = jax.lax.broadcasted_iota(jnp.int32, (mpp, 128), 0)
        bit = m % WORD
        in_word = (m // WORD) == jax.lax.broadcasted_iota(
            jnp.int32, (mpp, 128), 1)
        place = (1 << (bit % 16)).astype(jnp.float32)
        lo, hi = (jnp.dot(plane,
                          jnp.where(in_word & half, place,
                                    0.0).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.int32)
                  for half in (bit < 16, bit >= 16))          # [RB, 128]
        words = jax.lax.bitcast_convert_type((hi << 16) | lo, jnp.uint32)
        out_ref[...] = rtx_ref[...] | words


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
    mp = w * WORD
    mpp = _round_up(mp, 128)
    rb, nr, lb, nk = _blocks(f, lanes, mpp)
    rtx_p = jnp.pad(rtx, ((0, rb * nr - f), (0, 128 - w)))
    lane_p = jnp.pad(jnp.stack([flow, off, valid.astype(jnp.int32)]),
                     ((0, 5), (0, lb * nk - lanes)))

    row_spec = pl.BlockSpec((rb, 128), lambda i, k: (i, 0))
    out = pl.pallas_call(
        functools.partial(_nack_kernel, lanes=lanes, num_flows=f, mp=mp),
        grid=(nr, nk),
        in_specs=[row_spec, pl.BlockSpec((8, lb), lambda i, k: (0, k))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rb * nr, 128), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((rb, mpp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rtx_p, lane_p)
    return out[:f, :w]
