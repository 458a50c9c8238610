"""The fabric tick's hot-path ops.

Each op is a Pallas kernel with a pure-jnp oracle (ref.py). Which of the
two runs is decided once per trace, when a caller such as the fabric
tick is traced, from the process's default backend
(``jax.default_backend()``), not from the device a jit targets:

* TPU — the compiled Pallas kernel (``interpret=False``), always. No
  TPU path runs a kernel in interpret mode or falls back to the oracle.
* any other backend (the CPU test path) — the jnp oracle, which XLA
  fuses well there; Pallas interpret mode would be the slow path.

So on a TPU host a tick jitted for a CPU device still gets the Pallas
kernels, and a CPU host compiling for a described TPU gets the oracles
unless a test steers `_compiled_kernels`. Tests that validate a kernel
against its oracle off the chip call the kernel module with
``interpret=True`` and `ref` directly.
(``nscc_update.py`` and ``ecmp_hash.py`` hold kernels the tick does not
call; tests validate them against their oracles.)
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.nack_mark import nack_mark as _nack_mark_pallas
from repro.kernels.sack_bitmap import sack_advance as _sack_pallas
from repro.kernels.sack_fused import sack_fused as _sack_fused_pallas


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
