"""Pure-jnp oracles for the Pallas kernels.

These are the semantic ground truth: every kernel in this package is
validated against these functions across shape/dtype sweeps in
tests/test_kernels.py and tests/test_fabric_batch.py (interpret mode on
CPU); chip_smoke.py checks the tick's compiled kernels against them on
the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.cms.nscc import NSCCParams, window_delta
from repro.core.pds import shift_ring, trailing_ones
from repro.network.ecmp import ecmp_hash


def nscc_update_ref(cwnd: jax.Array, ecn: jax.Array, rtt: jax.Array,
                    count: jax.Array, params: NSCCParams) -> jax.Array:
    """Batched NSCC window update.

    cwnd: [N] f32 current windows; ecn: [N] bool aggregate ECN of the ACK
    round; rtt: [N] f32 measured RTT; count: [N] i32 number of coalesced
    ACKs this round (CACK/SACK may cover several packets, Sec. 3.2.5).
    """
    delta = window_delta(cwnd, ecn, rtt, params) * count.astype(jnp.float32)
    active = count > 0
    out = jnp.where(active, cwnd + delta, cwnd)
    return jnp.clip(out, params.min_cwnd, params.max_cwnd)


def sack_advance_ref(ring: jax.Array, base: jax.Array):
    """Cumulative-ACK advance over [N, W] uint32 SACK rings.

    Returns (new_ring, new_base, advanced): count the contiguous received
    prefix, shift it out, advance the base PSN (Sec. 3.2.5).
    """
    adv = trailing_ones(ring)
    return shift_ring(ring, adv), base + adv.astype(jnp.uint32), adv


def ecmp_hash_ref(src: jax.Array, dst: jax.Array, ev: jax.Array,
                  salt: jax.Array, fanout: int) -> jax.Array:
    """Batched ECMP port selection: H(fields) mod fanout (Sec. 2.1)."""
    return (ecmp_hash(src, dst, ev, salt) % jnp.uint32(fanout)).astype(jnp.int32)


def sack_fused_ref(ring: jax.Array, base: jax.Array, rtx: jax.Array,
                   mask: jax.Array):
    """Fused SACK hot path (Sec. 3.2.5): record-rx OR-apply, CACK advance,
    and lockstep shift of the SACK ring and the retransmit-pending bitmap.

    ring, rtx, mask: [N, W] uint32; base: [N] uint32.
    Returns (new_ring, new_base, new_rtx, advanced[int32]).
    """
    ring = ring | mask
    adv = trailing_ones(ring)
    return (shift_ring(ring, adv), base + adv.astype(jnp.uint32),
            shift_ring(rtx, adv), adv)


def nack_mark_ref(rtx: jax.Array, flow: jax.Array, off: jax.Array,
                  valid: jax.Array) -> jax.Array:
    """Duplicate-safe NACK retransmit-bit marking (Sec. 3.2.4).

    Lane l with valid[l] sets bit off[l] (a PSN offset in [0, W*32)) of
    ring row flow[l]; several lanes may hit one row, and two lanes may
    carry the SAME (flow, off) — e.g. a packet and its retransmission
    trimmed in one tick — so the combine must be OR, not add.

    rtx: [F, W] uint32; flow/off: [L] int32; valid: [L] bool.
    Returns rtx with the bits OR-ed in.

    Scheme: each lane drops one True on an [F, W*32] bool plane (masked
    lanes land on an out-of-range row), then the plane packs into ring
    words — bits are distinct powers of two per word, so the pack-sum IS
    the bitwise OR. E-Q scalar updates + an [F, mp] pack instead of the
    [F, W, L] dense OR-fold this replaced (the fabric tick's largest
    intermediate by an order of magnitude).
    """
    f, w = rtx.shape
    mp = w * 32
    rows = jnp.where(valid, flow, f)
    cols = jnp.clip(off, 0, mp - 1)
    plane = jnp.zeros((f, mp), jnp.bool_).at[rows, cols].set(True,
                                                             mode="drop")
    words = (plane.reshape(f, w, 32).astype(jnp.uint32)
             << jnp.arange(32, dtype=jnp.uint32)).sum(axis=2,
                                                      dtype=jnp.uint32)
    return rtx | words


def queue_write_ref(q_pkt: jax.Array, tq: jax.Array, wslot: jax.Array,
                    cand_pkt: jax.Array) -> jax.Array:
    """The enqueue write (fabric tick, ``tick.enqueue.write``): candidate
    i's packet record lands in slot wslot[i] of queue tq[i]; a candidate
    with tq[i] == Q (out of range) is dropped.

    q_pkt: [Q, C, K] int32; tq, wslot: [n] int32 (wslot in [0, C));
    cand_pkt: [n, K] int32. Returns q_pkt with the records written.
    """
    return q_pkt.at[tq, wslot].set(cand_pkt, mode="drop")
