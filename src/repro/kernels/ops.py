"""The fabric tick's hot-path ops.

Each op has a TPU path and a pure-jnp oracle (ref.py): a Pallas kernel
for `sack_advance`, `sack_fused` and `nack_mark`, and for `queue_write`
(the enqueue write) a one-hot contraction on the MXU in plain jnp
(queue_write.py). Which of the two runs is decided once per trace, when
a caller such as the fabric tick is traced, from the process's default
backend (``jax.default_backend()``), not from the device a jit targets:

* TPU — the compiled Pallas kernel (``interpret=False``), always. No
  TPU path runs a kernel in interpret mode or falls back to the oracle.
  `queue_write` takes its contraction while Q·n·C (queues x candidates
  x ring capacity) is at most `DENSE_WRITE_MAX`, and its scatter oracle
  past it: the contraction's work grows as Q·n·C, the scatter's as n.
* any other backend (the CPU test path) — the jnp oracle, which XLA
  fuses well there; Pallas interpret mode would be the slow path. For
  `queue_write` that is the scatter, cheap there.

`queue_write`'s contraction is exact, and equal to the scatter bit for
bit, because the enqueue gives each (queue, slot) at most one writer per
tick: a fitting candidate writes slot (head + length + its arrival rank)
% C of its queue, with length + rank < C, and one queue's ranks are
distinct. Each output element of the bf16 contraction over byte limbs
then has at most one nonzero term, a byte times 1 (queue_write.py;
DESIGN.md "Dense hot path").

So on a TPU host a tick jitted for a CPU device still gets the Pallas
kernels, and a CPU host compiling for a described TPU gets the oracles
unless a test steers `_compiled_kernels` (or `_dense_queue_write`, the
write's decision alone). Tests that validate a kernel against its oracle
off the chip call the kernel module with ``interpret=True`` (the
write's contraction as it is) and `ref` directly.
(``nscc_update.py`` and ``ecmp_hash.py`` hold kernels the tick does not
call; tests validate them against their oracles.)
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.nack_mark import nack_mark as _nack_mark_pallas
from repro.kernels.queue_write import queue_write as _queue_write_dense
from repro.kernels.sack_bitmap import sack_advance as _sack_pallas
from repro.kernels.sack_fused import sack_fused as _sack_fused_pallas

#: the largest Q x n x C (queues x candidates x capacity) the enqueue
#: write takes the dense contraction at; past it the scatter is cheaper
#: (measured on a v5e: DESIGN.md "Dense hot path")
DENSE_WRITE_MAX = 1 << 25


def _compiled_kernels() -> bool:
    """The dispatch decision, taken at trace time: compiled Pallas on a
    TPU backend, the jnp oracle everywhere else."""
    return jax.default_backend() == "tpu"


def sack_advance(ring, base):
    if _compiled_kernels():
        return _sack_pallas(ring, base)
    return ref.sack_advance_ref(ring, base)


def sack_fused(ring, base, rtx, mask):
    """Fused record-rx OR + CACK advance + dual ring shift (Sec. 3.2.5)."""
    if _compiled_kernels():
        return _sack_fused_pallas(ring, base, rtx, mask)
    return ref.sack_fused_ref(ring, base, rtx, mask)


def nack_mark(rtx, flow, off, valid):
    """Duplicate-safe OR of NACK-requested retransmit bits (Sec. 3.2.4)."""
    if _compiled_kernels():
        return _nack_mark_pallas(rtx, flow, off, valid)
    return ref.nack_mark_ref(rtx, flow, off, valid)


def _dense_queue_write() -> bool:
    """The enqueue write's backend decision, the same as
    `_compiled_kernels` (tests steer it alone): the dense contraction on
    a TPU, the scatter oracle everywhere else."""
    return _compiled_kernels()


def queue_write(q_pkt, tq, wslot, cand_pkt):
    """The enqueue write: candidate i's record into slot wslot[i] of
    queue tq[i], dropped where tq[i] == Q. At most one writer per slot."""
    q, c, _ = q_pkt.shape
    if _dense_queue_write() and q * tq.shape[0] * c <= DENSE_WRITE_MAX:
        return _queue_write_dense(q_pkt, tq, wslot, cand_pkt)
    return ref.queue_write_ref(q_pkt, tq, wslot, cand_pkt)
