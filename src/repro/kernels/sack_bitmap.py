"""Pallas TPU kernel: SACK-ring cumulative-ACK advance (Sec. 3.2.5).

Per PDC, the receiver keeps a ring bitmap of arrived PSNs anchored at the
CACK point. Every ACK-coalescing round the hardware must (a) count the
contiguous prefix of received packets, (b) advance the base PSN, and
(c) shift the ring down — across every active PDC. That is the hot loop
this kernel implements blockwise.

TPU adaptation: the per-row variable shift (a gather in the reference)
is re-expressed as a one-hot masked reduction — for output word j we sum
ring[:, k] * [k == j + word_shift] over k, a W x W contraction with
W = ring words (W <= 32), instead of a data-dependent gather which the
TPU vector unit cannot do across lanes. The advance and shift are
``sack_fused.cack_shift``, shared with the fused source-side kernel.

Block layout: (BLOCK_R rows) x (W words padded to 128 lanes) per grid
step; every operand tile lives in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sack_fused import BLOCK_R, cack_shift, store_advance


def _sack_kernel(ring_ref, base_ref, ring_out_ref, base_out_ref, adv_ref,
                 *, w: int):
    adv, (shifted,) = cack_shift(ring_ref[:, :w])
    ring_out_ref[:, :w] = shifted
    store_advance(base_ref, base_out_ref, adv_ref, adv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sack_advance(ring: jax.Array, base: jax.Array, interpret: bool = False):
    """CACK-advance every PDC's SACK ring.

    ring: [N, W] uint32 (W <= 32 words = up to 1024-PSN MP_RANGE window)
    base: [N] uint32
    Returns (new_ring, new_base, advanced[int32]). ``interpret=True``
    runs the body in the Pallas interpreter (CPU validation only).
    """
    n, w = ring.shape
    assert w <= 128
    rows = -(-n // BLOCK_R) * BLOCK_R
    padr = rows - n
    ring_p = jnp.pad(ring, ((0, padr), (0, 128 - w)))
    base_p = jnp.pad(base.reshape(-1, 1), ((0, padr), (0, 127)))

    grid = (rows // BLOCK_R,)
    spec128 = pl.BlockSpec((BLOCK_R, 128), lambda i: (i, 0))
    ring_o, base_o, adv_o = pl.pallas_call(
        functools.partial(_sack_kernel, w=w),
        grid=grid,
        in_specs=[spec128, spec128],
        out_specs=[spec128, spec128, spec128],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        ],
        interpret=interpret,
    )(ring_p, base_p)
    return ring_o[:n, :w], base_o[:n, 0], adv_o[:n, 0]
