"""Minimum HBM bytes each hot-path Pallas kernel moves per call.

One call per batch tick on each device, over that device's `lanes`
scenario lanes: the kernel must read every operand and write every
result once, at their logical shapes and types (no padding counted).
With F flows, W ring words (``mp_range / 32``, uint32) and L NACK lanes
(``Q + 2F``: the control lanes past the Q ACK lanes):

* ``sack_fused(ring, base, rtx, mask) -> (ring, base, rtx, adv)``:
  reads three [F, W] rings and the [F] base, writes two rings, the base
  and the [F] int32 advance.
* ``sack_advance(ring, base) -> (ring, base, adv)``: reads one ring and
  the base, writes the ring, the base and the advance.
* ``nack_mark(rtx, flow, off, valid) -> rtx``: reads the ring, two
  int32 and one bool per lane, writes the ring.
"""
from __future__ import annotations


def bytes_per_call(kernel: str, lanes: int, flows: int, words: int,
                   nack_lanes: int) -> int:
    ring = 4 * flows * words
    row = 4 * flows
    if kernel == "sack_fused":
        per_lane = (3 * ring + row) + (2 * ring + 2 * row)
    elif kernel == "sack_advance":
        per_lane = (ring + row) + (ring + 2 * row)
    elif kernel == "nack_mark":
        per_lane = (ring + 9 * nack_lanes) + ring
    else:
        raise ValueError(f"no byte count for kernel {kernel!r}")
    return lanes * per_lane


def roofline(kernel: str, lanes: int, flows: int, words: int,
             nack_lanes: int, calls: int, seconds: float,
             peak_bytes_per_s: float) -> float:
    """Share (in %) of the HBM roofline that `calls` calls taking
    `seconds` reach. A reading over 105% means the bytes are counted too
    high or the time leaves out part of the work, and raises."""
    if seconds <= 0 or calls <= 0:
        raise ValueError("a roofline needs calls and a positive time")
    moved = bytes_per_call(kernel, lanes, flows, words, nack_lanes) * calls
    share = 100.0 * moved / peak_bytes_per_s / seconds
    if share > 105.0:
        raise ValueError(f"{kernel}: {share:.1f}% of the HBM roofline is "
                         f"over 105%: the byte count or the time is wrong")
    return share
