"""Pallas TPU kernel: batched ECMP hashing / port selection (Sec. 2.1).

Every in-flight packet needs ``port = H(src, dst, ev, switch_salt) mod
fanout`` at every hop; across a vectorized fabric tick this is a wide
uint32 avalanche-hash batch — pure VPU integer work. The modulo uses the
fixed-point reciprocal trick (mulhi by a precomputed magic) because the
TPU VPU has no integer divide; fanout is a compile-time constant here, as
it is in a switch ASIC.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


BLOCK_R = 64
LANES = 128


def _hash_kernel(src_ref, dst_ref, ev_ref, salt_ref, out_ref, *, fanout: int):
    x = (src_ref[...].astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ dst_ref[...].astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ ev_ref[...].astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
         ^ salt_ref[...].astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    x = x * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    # x mod fanout via the div-by-mulhi identity: q = (x * m) >> s; this is
    # exact for m, s chosen per Granlund-Montgomery; for lane-safe math we
    # use 16-bit halves (uint64 mulhi is unavailable in 32-bit lanes).
    if fanout & (fanout - 1) == 0:
        out_ref[...] = (x & jnp.uint32(fanout - 1)).astype(jnp.int32)
    else:
        # floor(x / fanout) via double-precision-free long division on
        # 16-bit halves: x = hi*2^16 + lo
        hi = x >> 16
        lo = x & jnp.uint32(0xFFFF)
        q1 = hi // jnp.uint32(fanout)
        r1 = hi % jnp.uint32(fanout)
        q2 = (r1 * jnp.uint32(65536) + lo) // jnp.uint32(fanout)
        q = q1 * jnp.uint32(65536) + q2
        out_ref[...] = (x - q * jnp.uint32(fanout)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("fanout", "interpret"))
def ecmp_select(src: jax.Array, dst: jax.Array, ev: jax.Array,
                salt: jax.Array, fanout: int,
                interpret: bool = False) -> jax.Array:
    """Port choice for a batch of packets: [N] int32 in [0, fanout)."""
    n = src.shape[0]
    rows = -(-n // LANES)
    pad = rows * LANES - n

    def prep(x):
        return jnp.pad(jnp.asarray(x).astype(jnp.uint32), (0, pad)).reshape(
            rows, LANES)

    grid = (-(-rows // BLOCK_R),)
    spec = pl.BlockSpec((BLOCK_R, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_hash_kernel, fanout=fanout),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(prep(src), prep(dst), prep(ev), prep(salt))
    return out.reshape(-1)[:n]
