"""Pallas TPU kernel: fused SACK record-rx + CACK-advance + rtx shift.

The per-ACK-round hot loop of a UET source PDS (Sec. 3.2.5 + 3.2.4) is
three dense per-PDC ring operations in sequence:

  1. **record-rx** — OR the freshly SACKed PSN bits into the tracking ring
     (the lane->word scatter mask is built by XLA outside the kernel —
     data-dependent cross-lane scatter is not a TPU vector op — and
     applied here);
  2. **CACK-advance** — count the contiguous received prefix, advance the
     base PSN;
  3. **ring shifts** — funnel-shift *both* the SACK ring and the source's
     retransmit-pending bitmap down by the advance, keeping the two rings
     anchored at the same base.

Running them as separate kernels round-trips every ring through HBM three
times; fused, each [N, W] operand is read and written exactly once, and
the two funnel shifts share one pair of one-hot gather matrices (a W x W
masked reduction instead of a per-row variable gather, which the TPU
vector unit cannot do across lanes). ``sack_bitmap.py`` reuses the
advance and shift below without the record-rx and rtx operands.

Mosaic constraints the body is written around: it reduces no unsigned
integers (the one-hot selections sum the int32 bit patterns — each
output word selects at most one input word, so the sum is exact), and
it lowers no value-level scatter (results are stored through the
output ref's lane slice).

Block layout: (BLOCK_R rows) x (128 lanes) per grid step, all in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.pds import _popcount32

BLOCK_R = 64
WORD = 32


def _funnel_shift(ring, one_hot_lo, one_hot_hi, bits):
    """Per-row right-shift by (words, bits) using shared one-hot selectors."""
    ring_b = jax.lax.bitcast_convert_type(ring, jnp.int32)[:, None, :]
    lo, hi = (jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(sel, ring_b, 0), axis=2), jnp.uint32)
        for sel in (one_hot_lo, one_hot_hi))
    b = bits[:, None]
    return jnp.where(b == 0, lo,
                     (lo >> b) | (hi << (jnp.uint32(WORD) - b)))


def cack_shift(ring, *others):
    """CACK advance of each row of `ring` ([R, W] uint32) and the
    lockstep funnel shift of `ring` and every array in `others` by it.

    Returns (advanced [R] int32, [shifted ring, *shifted others])."""
    R, w = ring.shape
    inv = ~ring
    lsb = inv & (jnp.uint32(0) - inv)
    ctz = _popcount32(lsb - jnp.uint32(1))
    ctz = jnp.where(inv == jnp.uint32(0), WORD, ctz)          # all-ones word
    full = ring == jnp.uint32(0xFFFFFFFF)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, w), 1)
    first_partial = jnp.min(jnp.where(~full, col, w), axis=1)  # [R]
    sel = col == first_partial[:, None]
    partial_bits = jnp.sum(jnp.where(sel, ctz, 0), axis=1)
    adv = jnp.where(first_partial == w, w * WORD,
                    first_partial * WORD + partial_bits)       # [R]

    # lo[i, j] = ring[i, j + words[i]] ; hi[i, j] = ring[i, j + words[i] + 1]
    words = adv // WORD
    bits = (adv % WORD).astype(jnp.uint32)
    shift_idx = col + words[:, None]                           # [R, W]
    k = jax.lax.broadcasted_iota(jnp.int32, (R, w, w), 2)      # [R, W, W]
    one_hot_lo = k == shift_idx[:, :, None]
    one_hot_hi = k == (shift_idx + 1)[:, :, None]
    return adv, [_funnel_shift(x, one_hot_lo, one_hot_hi, bits)
                 for x in (ring, *others)]


def store_advance(base_ref, base_out_ref, adv_ref, adv):
    """Write base + adv and adv into column 0 of the per-row carriers."""
    base = base_ref[...]                                 # [R, 128] col 0 used
    col0 = (jax.lax.broadcasted_iota(jnp.int32, base.shape, 1) == 0)
    base_out_ref[...] = base + adv.astype(jnp.uint32)[:, None] * col0.astype(
        jnp.uint32)
    adv_ref[...] = adv[:, None] * col0


def _fused_kernel(ring_ref, base_ref, rtx_ref, mask_ref,
                  ring_out_ref, base_out_ref, rtx_out_ref, adv_ref,
                  *, w: int):
    ring = ring_ref[:, :w] | mask_ref[:, :w]             # 1. record-rx
    adv, (ring_s, rtx_s) = cack_shift(ring, rtx_ref[:, :w])  # 2. + 3.
    ring_out_ref[:, :w] = ring_s
    rtx_out_ref[:, :w] = rtx_s
    store_advance(base_ref, base_out_ref, adv_ref, adv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sack_fused(ring: jax.Array, base: jax.Array, rtx: jax.Array,
               mask: jax.Array, interpret: bool = False):
    """Fused record-rx / CACK-advance / dual ring shift over N PDCs.

    ring, rtx, mask: [N, W] uint32 (W <= 32 words); base: [N] uint32.
    `mask` carries the bits to OR into `ring` (built by pds.or_mask).
    Returns (new_ring, new_base, new_rtx, advanced[int32]).
    ``interpret=True`` runs the body in the Pallas interpreter (CPU
    validation only).
    """
    n, w = ring.shape
    assert rtx.shape == ring.shape and mask.shape == ring.shape
    assert w <= 128
    rows = -(-n // BLOCK_R) * BLOCK_R
    padr = rows - n
    pad2 = lambda a: jnp.pad(a, ((0, padr), (0, 128 - w)))
    base_p = jnp.pad(base.reshape(-1, 1), ((0, padr), (0, 127)))

    grid = (rows // BLOCK_R,)
    spec128 = pl.BlockSpec((BLOCK_R, 128), lambda i: (i, 0))
    ring_o, base_o, rtx_o, adv_o = pl.pallas_call(
        functools.partial(_fused_kernel, w=w),
        grid=grid,
        in_specs=[spec128, spec128, spec128, spec128],
        out_specs=[spec128, spec128, spec128, spec128],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        ],
        interpret=interpret,
    )(pad2(ring), base_p, pad2(rtx), pad2(mask))
    return ring_o[:n, :w], base_o[:n, 0], rtx_o[:n, :w], adv_o[:n, 0]
